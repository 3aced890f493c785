"""Ahead-of-time compiles of the serve path's Pallas kernels for a described
TPU v5e chip, at published widths. Nothing runs: the TPU compiler (Mosaic)
must accept each kernel — block shapes aligned to the (8, 128) tiling, VMEM
within its limit — and the compiled program must hold the kernel
(`tpu_custom_call`). Interpret mode, which every other test uses, checks
none of this.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library, and the test workers all
import this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch, flash_attn, harness, paged_attn

#: (name, K, N): Phi-3-mini's projections (phi-3-vision-4.2b), its
#: vocab-32,064 lm_head, and one deepseek-moe-16b expert (the EP shape)
GEMM_SHAPES = [("qkv", 3072, 9216), ("ffn_up", 3072, 16384),
               ("ffn_down", 8192, 3072), ("lm_head", 3072, 32064),
               ("moe_expert", 2048, 1408)]
BODY_CELLS = sorted((c for c in dispatch.cells().values()
                     if c.body is not None), key=lambda c: c.body.name)
M = 8                                   # a decode batch (M is padded to 8)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but can never be read back without one: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("shape", GEMM_SHAPES, ids=lambda s: s[0])
@pytest.mark.parametrize("cell", BODY_CELLS, ids=lambda c: c.body.name)
def test_qgemm_cell_compiles(one_chip, cell, shape):
    body, (_, k, n) = cell.body, shape
    sd = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
    xs = [sd((M, k // body.xk), jnp.uint32 if body.xk > 1 else jnp.int8)
          ] * body.n_x
    if body.w_stack:
        ws = [sd((body.w_stack, n, k // body.wk), jnp.uint32)] * body.n_w
    elif body.w_kmajor:
        ws = [sd((k, n), jnp.int8)] * body.n_w
    else:
        ws = [sd((n, k // body.wk), jnp.uint32)] * body.n_w
    tile = dispatch.default_tune().tile_for(cell.op)
    text = _compile_text(
        lambda x, w, wsc, asc, b: harness.gemm(
            body, tuple(x), tuple(w), wsc, asc, b, k=k, tile=tile,
            interpret=False),
        xs, ws, sd((n,), jnp.float32), sd((M,), jnp.float32),
        sd((n,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("hq,hk,dh", [(32, 32, 96), (32, 8, 128)],
                         ids=["phi3-dh96", "gqa-dh128"])
def test_paged_attn_compiles(one_chip, hq, hk, dh, kv_dtype):
    sd = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
    slots, page, max_pages = 4, 32, 16
    pool = sd((slots * max_pages + 1, page, hk, dh), kv_dtype)
    text = _compile_text(
        lambda q, k, v, pg, pos: paged_attn.paged_flash_decode(
            q, k, v, pg, pos, interpret=False),
        sd((slots, hq, dh), jnp.bfloat16), pool, pool,
        sd((slots, max_pages), jnp.int32), sd((slots,), jnp.int32))
    assert "tpu_custom_call" in text


def test_decode_program_updates_pool_in_place(one_chip, monkeypatch):
    """Phi-3's decode step at published widths, four layers (two scanned),
    cache donated as `Server` donates it, compiled for the chip: the cache
    output aliases its input, the paged kernel is there, and no slice,
    update or copy has a layer's pool (or the stacked pools) as its result
    — the scan carries the pool and each layer writes one token into it."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.launch.serve import serve_params
    from repro.models import attention, transformer
    from repro.models.common import ModelCtx

    monkeypatch.setattr(dispatch, "pallas_interpret", lambda: False)
    monkeypatch.setattr(attention, "pallas_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("phi-3-vision-4.2b"), n_layers=4,
                              policy="mixed")
    sp = transformer.build_specs(cfg)
    ctx = ModelCtx(mode="serve", backend="pallas")
    slots, page, cache_len, num_pages = 8, 32, 3072, 257
    sd = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sd, jax.eval_shape(
        lambda: serve_params(cfg, seed=0)[0]))
    cache = jax.tree.map(sd, transformer.cache_shapes(
        cfg, slots, cache_len, paged=(num_pages, page),
        kv_dtype=jnp.bfloat16))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    text = jax.jit(
        lambda p, c, t, pos, pg: transformer.decode_step(
            p, c, t, pos, sp, ctx, pages=pg),
        donate_argnums=(1,)).lower(
            params, cache, i32(slots, 1), i32(slots),
            i32(slots, cache_len // page)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "input_output_alias={" in text.split("\n", 1)[0]
    pool = f"{num_pages},{page},{cfg.n_kv_heads},{cfg.head_dim}]"
    moved = []
    for line in text.splitlines():
        m = re.search(r" = (.+?) ([a-z][a-z0-9-]*)\(", line)
        if (m and pool in m.group(1) and m.group(2) in (
                "dynamic-slice", "dynamic-update-slice", "copy",
                "copy-start", "copy-done")):
            moved.append(line.strip()[:160])
    assert not moved, moved


def test_flash_attn_compiles(one_chip):
    qkv = jax.ShapeDtypeStruct((32, 256, 96), jnp.bfloat16, sharding=one_chip)
    text = _compile_text(
        lambda q, k, v: flash_attn.flash_attention(q, k, v, interpret=False),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backend,bucket", [("pallas", 64), ("jnp", 64),
                                            ("jnp", 256)])
def test_prefill_rounds_without_excess_precision(one_chip, monkeypatch,
                                                 backend, bucket):
    """chip_smoke.py holds the Pallas and jnp prefill programs to equal
    first tokens, so their numerics must not depend on how XLA fuses them.
    With excess precision allowed (XLA's default) the TPU compiler keeps
    bf16 arithmetic unrounded inside a fusion; compiled without it, it
    rounds each such op with a reduce-precision. Where the model rounds
    explicitly (`dispatch.round_to`), turning excess precision off adds no
    reduce-precision op. Phi-3 widths, two layers (int8 first and last
    blocks). The flash bucket of the Pallas path is left out: there the
    switch also marks value-preserving bf16 concatenates."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.launch.serve import serve_params
    from repro.models import attention, transformer
    from repro.models.common import ModelCtx

    monkeypatch.setattr(dispatch, "pallas_interpret", lambda: False)
    monkeypatch.setattr(attention, "pallas_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("phi-3-vision-4.2b"), n_layers=2,
                              policy="mixed")
    sp = transformer.build_specs(cfg)
    ctx = ModelCtx(mode="serve", backend=backend)
    sd = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sd, jax.eval_shape(
        lambda: serve_params(cfg, seed=0)[0]))
    args = (params, sd(jnp.zeros((1, bucket), jnp.int32)),
            sd(jnp.zeros((1,), jnp.int32)))
    lowered = jax.jit(lambda p, t, lp: transformer.prefill(
        p, t, sp, ctx, cache_len=512, last_pos=lp)).lower(*args)
    count = lambda opts: len(re.findall(
        r" reduce-precision\(", lowered.compile(opts).as_text()))
    assert count({"xla_allow_excess_precision": False}) == count(None)
