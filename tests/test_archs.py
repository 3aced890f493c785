"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes + no NaNs; plus prefill/decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import registry, transformer
from repro.models.common import ModelCtx, TRAIN

SERVE = ModelCtx(mode="serve")


@pytest.fixture(scope="module")
def built():
    """Init each reduced arch once per module."""
    cache = {}

    def get(name):
        if name not in cache:
            cfg = get_config(name).reduced()
            sp = transformer.build_specs(cfg)
            params = transformer.init(jax.random.PRNGKey(0), cfg)
            cache[name] = (cfg, sp, params)
        return cache[name]
    return get


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nan(arch, built):
    cfg, sp, params = built(arch)
    b, t = 2, 32
    batch = registry.make_batch(jax.random.PRNGKey(1), cfg, b, t)
    logits, aux, prefix = transformer.forward(
        params, batch["tokens"], sp, TRAIN, frontend_embeds=batch.get("frontend"))
    exp_t = t + (cfg.frontend_len if cfg.frontend == "vision" else 0)
    assert logits.shape == (b, exp_t, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(logits))), arch
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_grads_finite(arch, built):
    cfg, sp, params = built(arch)
    batch = registry.make_batch(jax.random.PRNGKey(2), cfg, 2, 16)
    (loss, _), grads = jax.value_and_grad(transformer.loss_fn, has_aux=True)(
        params, batch, sp, TRAIN)
    assert np.isfinite(float(loss)), arch
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat), arch
    # at least one nonzero grad per block group
    total = sum(float(jnp.sum(jnp.abs(g))) for g in flat)
    assert total > 0, arch


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch, built):
    """decode(prefill(prompt)) logits == forward(prompt+token) logits.

    Run in f32 so the check verifies the *algebra* (cache layout, ring
    buffers, recurrent state handoff) — in bf16 the two equivalent attention
    formulations accumulate ~1e-2 noise per layer which is not a bug.
    """
    cfg, sp, params = built(arch)
    f32 = ModelCtx(mode="train", dtype=jnp.float32)
    b, t = 2, 16
    batch = registry.make_batch(jax.random.PRNGKey(3), cfg, b, t + 1)
    tokens = batch["tokens"]
    fe = batch.get("frontend")

    logits_all, _, prefix = transformer.forward(params, tokens, sp, f32,
                                                frontend_embeds=fe)
    xlen = t + (cfg.frontend_len if cfg.frontend == "vision" else 0)
    lp, cache = transformer.prefill(params, tokens[:, :t], sp, f32,
                                    frontend_embeds=fe, cache_len=xlen + 4)
    ld, _ = transformer.decode_step(params, cache, tokens[:, t:t + 1],
                                    jnp.int32(xlen), sp, f32)
    want = np.asarray(logits_all[:, prefix + t], np.float64)
    got = np.asarray(ld[:, 0], np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "phi3.5-moe-42b-a6.6b"])
def test_moe_router_topk_shapes(arch, built):
    """Router/top-k geometry on the reduced MoE archs: the router spec is a
    (D -> E) linear, each token lands exactly top_k assignments, and the aux
    counters account for every one (kept + dropped == B*S*top_k)."""
    from repro.core.precision import get_policy
    from repro.models import moe

    cfg, sp, params = built(arch)
    pol = get_policy(cfg.policy)
    specs = moe.moe_specs(cfg, pol)
    assert specs.router.in_dim == cfg.d_model
    assert specs.router.out_dim == cfg.n_experts
    assert 0 < specs.top_k <= specs.n_experts

    p = moe.moe_init(jax.random.PRNGKey(5), specs)
    b, s = 2, 8
    x = jax.random.normal(jax.random.PRNGKey(6), (b, s, cfg.d_model),
                          jnp.float32)
    y, aux = moe.moe_apply(p, x, specs, ModelCtx(mode="train",
                                                 dtype=jnp.float32))
    assert y.shape == x.shape
    assert not bool(jnp.any(jnp.isnan(y))), arch
    et = np.asarray(aux["expert_tokens"])
    assert et.shape == (cfg.n_experts,) and et.dtype == np.int32
    assert int(et.sum()) + int(aux["dropped"]) == b * s * specs.top_k
    assert np.isfinite(float(aux["loss"]))


def test_moe_shared_expert_path(built):
    """deepseek's always-on shared expert really contributes: its reduced
    config keeps one shared expert (params carry a 'shared' FFN whose spec
    widens d_ff by n_shared), and zeroing that FFN changes the block output.
    phi3.5 has no shared expert — no 'shared' leaf, same top-level keys
    otherwise."""
    from repro.core.precision import get_policy
    from repro.models import moe

    cfg, _, _ = built("deepseek-moe-16b")
    assert cfg.n_shared_experts == 1
    pol = get_policy(cfg.policy)
    specs = moe.moe_specs(cfg, pol)
    assert specs.shared is not None
    p = moe.moe_init(jax.random.PRNGKey(7), specs)
    assert "shared" in p
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 8, cfg.d_model),
                          jnp.float32)
    ctx = ModelCtx(mode="train", dtype=jnp.float32)
    y, _ = moe.moe_apply(p, x, specs, ctx)
    p0 = dict(p, shared=jax.tree.map(jnp.zeros_like, p["shared"]))
    y0, _ = moe.moe_apply(p0, x, specs, ctx)
    assert bool(jnp.any(y != y0))

    cfg_phi, _, _ = built("phi3.5-moe-42b-a6.6b")
    specs_phi = moe.moe_specs(cfg_phi, get_policy(cfg_phi.policy))
    assert specs_phi.shared is None
    p_phi = moe.moe_init(jax.random.PRNGKey(9), specs_phi)
    assert "shared" not in p_phi
    assert set(p_phi) == set(p) - {"shared"}


@pytest.mark.parametrize("arch", ["llama3.2-3b", "xlstm-125m", "recurrentgemma-9b",
                                  "deepseek-moe-16b", "phi3.5-moe-42b-a6.6b"])
def test_serve_packed_forward(arch, built):
    """pack_for_serve params run the serve path without NaNs."""
    cfg, sp, params = built(arch)
    sparams = transformer.pack_for_serve(params, cfg)
    b, t = 2, 16
    batch = registry.make_batch(jax.random.PRNGKey(4), cfg, b, t)
    logits, cache = transformer.prefill(sparams, batch["tokens"], sp, SERVE,
                                        frontend_embeds=batch.get("frontend"))
    assert logits.shape == (b, 1, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(logits))), arch
    ld, _ = transformer.decode_step(sparams, cache, batch["tokens"][:, :1],
                                    jnp.int32(t), sp, SERVE)
    assert not bool(jnp.any(jnp.isnan(ld))), arch


@pytest.mark.parametrize("arch,policy", [("llama3.2-3b", "int8"),
                                         ("recurrentgemma-9b", "w4a8"),
                                         ("deepseek-moe-16b", "mixed")])
def test_init_packed_matches_pack_of_init(arch, policy):
    """The period-at-a-time build equals pack_for_serve(init) up to the
    last-ulp float differences of a differently compiled program: same
    tree, shapes and dtypes; floats within a few ulp; codes on a rounding
    boundary may flip, nothing else."""
    import dataclasses
    cfg = dataclasses.replace(get_config(arch).reduced(), policy=policy,
                              n_layers=5)
    k = jax.random.PRNGKey(3)
    got = transformer.init_packed(k, cfg, plane_twins=True)
    want = transformer.pack_for_serve(transformer.init(k, cfg), cfg,
                                      plane_twins=True)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and g.dtype == w.dtype
        if np.issubdtype(g.dtype, np.floating) or g.dtype == jnp.bfloat16:
            ulp = 2.0 ** -7 if g.dtype == jnp.bfloat16 else 2.0 ** -20
            np.testing.assert_allclose(g.astype(np.float32),
                                       w.astype(np.float32), rtol=ulp,
                                       atol=0)
        else:
            assert np.mean(g != w) < 1e-3


@pytest.mark.parametrize("arch", ARCHS)
def test_cache_shapes_match_init_cache(arch, built):
    cfg, sp, params = built(arch)
    shapes = transformer.cache_shapes(cfg, 2, 32)
    cache = transformer.init_cache(cfg, 2, 32)
    flat_s = jax.tree.leaves(shapes)
    flat_c = jax.tree.leaves(cache)
    assert len(flat_s) == len(flat_c)
    for s, c in zip(flat_s, flat_c):
        assert s.shape == c.shape and s.dtype == c.dtype


def test_full_config_param_counts():
    """Analytic N roughly matches the published sizes (sanity of configs)."""
    approx = {"nemotron-4-340b": 340e9, "qwen1.5-32b": 32e9, "llama3.2-3b": 3.2e9,
              "gemma3-4b": 4e9, "phi-3-vision-4.2b": 4e9,
              "phi3.5-moe-42b-a6.6b": 42e9, "deepseek-moe-16b": 16e9,
              "whisper-tiny": 37e6, "xlstm-125m": 125e6, "recurrentgemma-9b": 9e9}
    for arch, want in approx.items():
        n = get_config(arch).n_params()
        assert 0.4 * want < n < 2.1 * want, (arch, n, want)
