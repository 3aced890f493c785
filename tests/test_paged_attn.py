"""Paged-attention decode kernel vs the jnp gather path, at the
attention-output level.

Unit bar: tight f32 allclose. The kernel's online-softmax block accumulation
is the same algebra as the gather path's dense softmax at a different
reduction/normalization order (running-max rescales, block-grouped sums,
normalize-then-dot), so bitwise equality is not attainable here by
construction; the serving oracle suites (test_serving*.py with the pallas
backend) hold the token-exact bar on the decoded-token level.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.llama3_2_3b import CONFIG as LLAMA
from repro.core.precision import get_policy
from repro.kernels import paged_attn as paged_attn_mod
from repro.kernels.dispatch import default_tune
from repro.kernels.paged_attn import (TUNE_KEY, paged_flash_decode,
                                      resolve_pages_per_block,
                                      vmem_decode_tile_bytes)
from repro.models import attention
from repro.models.attention import (KV_SCALE, _kv_dequant, _kv_quant,
                                    attn_decode, attn_init, attn_specs,
                                    init_cache_shapes)
from repro.models.common import ModelCtx

TOL = dict(rtol=2e-5, atol=2e-5)


def _gather_ref(q, k_pool, v_pool, pages, pos):
    """The attn_decode gather-path algebra, isolated (dense softmax)."""
    b, hq, dh = q.shape
    _, p_, hk, _ = k_pool.shape
    s = pages.shape[1] * p_
    kf = _kv_dequant(k_pool[pages].reshape(b, s, hk, dh), q.dtype)
    vf = _kv_dequant(v_pool[pages].reshape(b, s, hk, dh), q.dtype)
    valid = jnp.arange(s)[None, :] <= pos[:, None]
    g = hq // hk
    qg = q.reshape(b, hk, g, dh)
    sc = jnp.einsum("bhgd,bshd->bhgs", qg, kf).astype(jnp.float32) / dh ** 0.5
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    a = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgs,bshd->bhgd", a, vf)
    return o.reshape(b, hq, dh)


def _setup(seed, b, max_pages, page_size, hk, hq, dh, int8, *,
           num_pages=None, dtype=jnp.float32):
    """Random pool + a disjoint per-row page layout + staggered positions."""
    num_pages = num_pages or (1 + b * max_pages)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, hq, dh), dtype)
    if int8:
        kp = jax.random.randint(ks[1], (num_pages, page_size, hk, dh),
                                -127, 128, jnp.int8)
        vp = jax.random.randint(ks[2], (num_pages, page_size, hk, dh),
                                -127, 128, jnp.int8)
    else:
        kp = jax.random.normal(ks[1], (num_pages, page_size, hk, dh), dtype)
        vp = jax.random.normal(ks[2], (num_pages, page_size, hk, dh), dtype)
    # row r owns pages [1 + r*max_pages, ...); unallocated columns -> 0
    pos = ((jax.random.randint(ks[3], (b,), 0, max_pages * page_size)
            ).astype(jnp.int32))
    pages = np.zeros((b, max_pages), np.int32)
    for r in range(b):
        n_active = int(pos[r]) // page_size + 1
        pages[r, :n_active] = 1 + r * max_pages + np.arange(n_active)
    return q, kp, vp, jnp.asarray(pages), pos


@pytest.mark.parametrize("b,max_pages,page_size,hk,hq,dh", [
    (2, 8, 4, 4, 4, 32),      # MHA
    (3, 8, 4, 2, 4, 32),      # GQA g=2 (the reduced-llama serve geometry)
    (2, 16, 8, 1, 4, 64),     # MQA, bigger pages
])
@pytest.mark.parametrize("int8", [False, True])
def test_kernel_matches_gather(b, max_pages, page_size, hk, hq, dh, int8):
    q, kp, vp, pages, pos = _setup(b * max_pages + dh, b, max_pages,
                                   page_size, hk, hq, dh, int8)
    got = paged_flash_decode(q, kp, vp, pages, pos, pages_per_block=4,
                             kv_scale=KV_SCALE)
    want = _gather_ref(q, kp, vp, pages, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("hk,hq", [(4, 4), (2, 4)], ids=["mha", "gqa"])
def test_stacked_pool_reads_its_layer(layer, kv, hk, hq):
    """A stacked (L, num_pages, P, Hk, dh) pool read at `layer` is the
    4-D call on pool[layer], bit for bit: the layer index only moves the
    page DMAs."""
    b, max_pages, page_size, dh, n_layers = 3, 8, 4, 32, 5
    int8 = kv == "int8"
    dtype = jnp.float32 if int8 else jnp.bfloat16
    q, _, _, pages, pos = _setup(7 + layer, b, max_pages, page_size, hk, hq,
                                 dh, int8, dtype=dtype)
    pools = [_setup(100 * i + layer, b, max_pages, page_size, hk, hq, dh,
                    int8, dtype=dtype)[1:3] for i in range(n_layers)]
    kst = jnp.stack([k for k, _ in pools])
    vst = jnp.stack([v for _, v in pools])
    got = paged_flash_decode(q, kst, vst, pages, pos, jnp.int32(layer),
                             pages_per_block=4, kv_scale=KV_SCALE)
    want = paged_flash_decode(q, kst[layer], vst[layer], pages, pos,
                              pages_per_block=4, kv_scale=KV_SCALE)
    assert jnp.array_equal(got, want)


def test_block_size_invariance():
    q, kp, vp, pages, pos = _setup(11, 2, 8, 4, 2, 4, 32, False)
    outs = [paged_flash_decode(q, kp, vp, pages, pos, pages_per_block=bkp,
                               kv_scale=KV_SCALE) for bkp in (1, 2, 4, 8)]
    want = _gather_ref(q, kp, vp, pages, pos)
    for got in outs:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_pos_zero_and_full():
    """Edge positions: a slot with only token 0 valid, one with every page."""
    q, kp, vp, pages, _ = _setup(5, 2, 8, 4, 2, 4, 32, False)
    pages = jnp.asarray(np.tile(1 + np.arange(8, dtype=np.int32), (2, 1)))
    pos = jnp.asarray([0, 31], jnp.int32)
    got = paged_flash_decode(q, kp, vp, pages, pos, pages_per_block=4,
                             kv_scale=KV_SCALE)
    want = _gather_ref(q, kp, vp, pages, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_shared_prefix_pages():
    """Prefix sharing: one physical page in SEVERAL table rows — the kernel
    (like the gather path) must be oblivious to the aliasing."""
    q, kp, vp, _, _ = _setup(13, 3, 8, 4, 2, 8, 32, False)
    pages = np.zeros((3, 8), np.int32)
    pages[:, :2] = [1, 2]                      # shared prompt prefix
    pages[0, 2:5] = [3, 4, 5]                  # distinct tails
    pages[1, 2:4] = [6, 7]
    pages[2, 2] = 8
    pages = jnp.asarray(pages)
    pos = jnp.asarray([18, 15, 9], jnp.int32)
    got = paged_flash_decode(q, kp, vp, pages, pos, pages_per_block=2,
                             kv_scale=KV_SCALE)
    want = _gather_ref(q, kp, vp, pages, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_bf16_query():
    q, kp, vp, pages, pos = _setup(17, 2, 8, 4, 2, 4, 32, False)
    qb = q.astype(jnp.bfloat16)
    got = paged_flash_decode(qb, kp, vp, pages, pos, pages_per_block=4,
                             kv_scale=KV_SCALE)
    assert got.dtype == jnp.bfloat16
    want = _gather_ref(qb, kp, vp, pages, pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# module level: attn_decode routing (fused vs gather), window bypass, bound
# ---------------------------------------------------------------------------

CFG = LLAMA.reduced()                         # 4 heads / 2 kv heads / dh 32
POL = get_policy(CFG.policy)
SPECS = attn_specs(CFG, POL)
PARAMS = attn_init(jax.random.PRNGKey(0), CFG, SPECS, jnp.float32)
CTX_GATHER = ModelCtx(mode="train", dtype=jnp.float32, paged_attn="gather")
CTX_FUSED = dataclasses.replace(CTX_GATHER, paged_attn="fused")


def _paged_inputs(seed, b=3, max_pages=8, page_size=4, int8=False):
    hk, dh = CFG.n_kv_heads, CFG.head_dim
    num_pages = 1 + b * max_pages
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (b, 1, CFG.d_model), jnp.float32)
    cd = jnp.int8 if int8 else jnp.float32
    cache = {
        "k": _kv_quant(jax.random.normal(
            ks[1], (num_pages, page_size, hk, dh), jnp.float32), cd),
        "v": _kv_quant(jax.random.normal(
            ks[2], (num_pages, page_size, hk, dh), jnp.float32), cd),
    }
    pos = jnp.asarray([2, 13, 30], jnp.int32)[:b]
    pages = np.zeros((b, max_pages), np.int32)
    for r in range(b):
        n_active = int(pos[r]) // page_size + 1
        pages[r, :n_active] = 1 + r * max_pages + np.arange(n_active)
    return x, cache, pos, jnp.asarray(pages)


@pytest.mark.parametrize("int8", [False, True])
def test_attn_decode_fused_matches_gather(int8):
    x, cache, pos, pages = _paged_inputs(23, int8=int8)
    out_g, c_g = attn_decode(PARAMS, x, cache, pos, SPECS, CFG, CTX_GATHER,
                             pages=pages)
    out_f, c_f = attn_decode(PARAMS, x, cache, pos, SPECS, CFG, CTX_FUSED,
                             pages=pages)
    # the cache WRITE side is shared code — bitwise identical
    assert jnp.array_equal(c_g["k"], c_f["k"])
    assert jnp.array_equal(c_g["v"], c_f["v"])
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_f), **TOL)


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
@pytest.mark.parametrize("int8", [False, True])
def test_attn_decode_stacked_pool(layer, int8):
    """attn_decode on a stacked pool at `layer`: the write lands in that
    layer only, the same bytes the 4-D call writes to pool[layer]; the
    gather path reads what it reads on pool[layer], and the fused path
    agrees with it."""
    x, cache, pos, pages = _paged_inputs(37, int8=int8)
    others = _paged_inputs(41, int8=int8)[1]
    stacked = {n: jnp.stack([others[n], cache[n], others[n]]) for n in "kv"}
    layer_cache = {n: stacked[n][layer] for n in "kv"}
    out_1, c_1 = attn_decode(PARAMS, x, layer_cache, pos, SPECS, CFG,
                             CTX_GATHER, pages=pages)
    out_g, c_g = attn_decode(PARAMS, x, stacked, pos, SPECS, CFG, CTX_GATHER,
                             pages=pages, layer=jnp.int32(layer))
    out_f, c_f = attn_decode(PARAMS, x, stacked, pos, SPECS, CFG, CTX_FUSED,
                             pages=pages, layer=jnp.int32(layer))
    for n in "kv":
        want = stacked[n].at[layer].set(c_1[n])
        assert jnp.array_equal(c_g[n], want)
        assert jnp.array_equal(c_f[n], want)
    assert jnp.array_equal(out_g, out_1)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_g), **TOL)


def test_attn_decode_eager_length_bound(monkeypatch):
    """Satellite: eager callers slice the table to max(pos)//P + 1 columns
    before either read path touches it."""
    captured = {}
    real = paged_attn_mod.paged_flash_decode

    def spy(q, kp, vp, pages, pos, **kw):
        captured["width"] = pages.shape[1]
        return real(q, kp, vp, pages, pos, **kw)

    monkeypatch.setattr(paged_attn_mod, "paged_flash_decode", spy)
    x, cache, pos, pages = _paged_inputs(29)
    assert int(jnp.max(pos)) == 30 and pages.shape[1] == 8
    out_f, _ = attn_decode(PARAMS, x, cache, pos, SPECS, CFG, CTX_FUSED,
                           pages=pages)
    assert captured["width"] == int(jnp.max(pos)) // 4 + 1 == 8
    # with a short batch the bound actually bites
    pos2 = jnp.asarray([2, 6, 5], jnp.int32)
    out2, _ = attn_decode(PARAMS, x, cache, pos2, SPECS, CFG, CTX_FUSED,
                          pages=pages)
    assert captured["width"] == 2
    # and the sliced gather path agrees with the fused one
    out2_g, _ = attn_decode(PARAMS, x, cache, pos2, SPECS, CFG, CTX_GATHER,
                            pages=pages)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out2_g), **TOL)


def test_windowed_layer_bypasses_pool():
    """Window layers under a paged model keep their ring slabs: `pages` must
    be ignored entirely (reads AND writes) when window > 0."""
    w = 8
    b, hk, dh = 2, CFG.n_kv_heads, CFG.head_dim
    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    x = jax.random.normal(ks[0], (b, 1, CFG.d_model), jnp.float32)
    ring = {"k": jax.random.normal(ks[1], (b, w, hk, dh), jnp.float32),
            "v": jax.random.normal(ks[2], (b, w, hk, dh), jnp.float32)}
    pos = jnp.asarray([5, 21], jnp.int32)
    pages = jnp.asarray(np.arange(2 * 8, dtype=np.int32).reshape(2, 8))
    out_np, c_np = attn_decode(PARAMS, x, ring, pos, SPECS, CFG, CTX_FUSED,
                               window=w, pages=None)
    out_pg, c_pg = attn_decode(PARAMS, x, ring, pos, SPECS, CFG, CTX_FUSED,
                               window=w, pages=pages)
    assert jnp.array_equal(out_np, out_pg)
    assert jnp.array_equal(c_np["k"], c_pg["k"])
    assert jnp.array_equal(c_np["v"], c_pg["v"])


def test_init_cache_shapes_window_stays_slab():
    paged = (64, 4)
    full = init_cache_shapes(CFG, 2, 32, 0, paged=paged)
    assert full["k"].shape == (64, 4, CFG.n_kv_heads, CFG.head_dim)
    ring = init_cache_shapes(CFG, 2, 32, 8, paged=paged)
    assert ring["k"].shape == (2, 8, CFG.n_kv_heads, CFG.head_dim)


def test_tune_table_entry():
    """The shipped TuneTable carries the paged-attn pseudo-cell."""
    tune = default_tune()
    assert TUNE_KEY in tune.tiles
    assert resolve_pages_per_block(tune) == tune.tiles[TUNE_KEY].bkq
    assert resolve_pages_per_block(None) >= 1
    # VMEM model sanity: one 4-page f32 tile at the reduced-llama geometry
    assert vmem_decode_tile_bytes(4, 2, 32, 4, 4, kv_bytes=4) > 0
