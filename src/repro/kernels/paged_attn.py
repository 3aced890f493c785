"""Paged-attention decode Pallas kernel — flash-decode over the page table.

The serve stack's decode attention (`models.attention.attn_decode(pages=)`)
reads a slot's KV through a (B, max_pages) page table into the shared block
pool (launch/kv_cache.py). The jnp oracle path gathers every page into a
dense (B, max_pages*page_size, Hk, dh) view and runs dense attention — each
decode step materializes (and dequantizes) the whole per-slot pool footprint
regardless of the slot's actual length. BrainTTA's thesis (and the
operand-fetch argument of the Molendijk/Corporaal survey) is that the data
movement belongs *inside* the compute loop; this kernel is that move for
decode:

  grid (slot, kv-page-block), page-block innermost (output-stationary in the
  slot). The page table and per-slot positions ride in as scalar-prefetch
  operands; the K and V pools each enter as `bkp` page-sized BlockSpec
  operands whose index maps read `pages[b, j*bkp + i]`, so the Pallas
  pipeline DMAs exactly the table's pages into VMEM. A page block is the
  whole (page_size, Hk, dh) trailing extent, which Mosaic accepts at any
  head_dim (a hand-issued DMA of a head_dim-96 slice is refused). Each
  page is dequantized in-register (`_kv_quant`/`_kv_dequant` algebra: int8
  codes at the static KV scale, passthrough otherwise) and folded into the
  online-softmax carries (m/l/acc in VMEM scratch — the
  `flash_attn._flash_kernel` structure: init on the first block, epilogue
  `acc / max(l, eps)` on the last). GQA: query heads arrive grouped
  (Hk, G, dh) and contract against the Hk kv heads of the page, both
  einsums accumulating in f32.

Early bound: each page's index map clamps its table column to the slot's
last active page, `min(col, pos // page_size)`. Past it the block index
repeats, so the pipeline issues no new DMA, and `pl.when(start <= pos)`
skips the math — short slots stop READING at their last active page; only
the (cheap) grid iteration continues to max_pages. Unallocated table
entries at or before `pos` point at page 0 (the pool's scratch page) whose
tokens the `tok <= pos` mask discards, exactly like the gather path.

The tunable is `Tile.bkq` = pages per kv block (`bm`/`bn` are unused for
this key), registered in the shipped TuneTable under the pseudo-cell key
"paged_attn/decode/*" (kernel_bench --retune sweeps it). VMEM working set
per step = 2 * bkp * page_size * Hk * dh operand bytes (double-buffered by
the pipeline) + the (Hq,)+(Hq,dh) f32 carries — `vmem_decode_tile_bytes`
is the bench model.

Stacked pools: the decode step's scanned layers carry their K and V pools
as one (L, num_pages, page_size, Hk, dh) array each (models/transformer.py
`decode_step`), and the kernel reads layer `layer` of it directly: the
layer index is a third scalar-prefetch operand and the page index maps
return (layer, page, 0, 0, 0). Slicing the layer out first would make XLA
materialise the whole layer pool as the custom call's operand every layer.
A 4-D pool is the one-layer case (layer 0 of a leading unit axis, a
bitcast).

CoW / prefix-sharing contract: identical to the gather path — the kernel
only READS through `pages`; the scheduler forks shared pages before the
decode write lands (launch/serve.py `_prepare_pages`), and this kernel runs
on the post-fork table the server passes to the decode step.

Exactness: validated against the gather path at the attention-output level
(tests/test_paged_attn.py, tight f32 tolerance — the online-softmax
block accumulation is the same algebra at a different reduction order, so
bitwise equality is not the contract there; the serving oracle suites'
token-exactness with the kernel enabled is).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .harness import Tile, fit_block

NEG_INF = -1e30

#: TuneTable pseudo-cell key for this kernel (same "w/a/impl" key shape as
#: the qgemm cells; only Tile.bkq — pages per kv block — is meaningful).
TUNE_KEY = ("paged_attn", "decode", "*")
DEFAULT_PAGES_PER_BLOCK = 4


def resolve_pages_per_block(tune=None) -> int:
    """Pages-per-kv-block from a TuneTable (the shipped one by default)."""
    if tune is None:
        from .dispatch import default_tune
        tune = default_tune()
    tile = tune.tiles.get(TUNE_KEY)
    if tile is None or tile.bkq is None:
        return DEFAULT_PAGES_PER_BLOCK
    return int(tile.bkq)


def vmem_decode_tile_bytes(page_size: int, hk: int, dh: int, hq: int,
                           bkp: int, kv_bytes: int = 1) -> int:
    """VMEM working set of one grid step (the kernel_bench tile model):
    K+V page tiles in the pool dtype, their f32 dequantized values, the q
    tile and the online-softmax carries."""
    t = bkp * page_size
    return (2 * t * hk * dh * kv_bytes      # K/V scratch tiles (pool dtype)
            + 2 * t * hk * dh * 4           # dequantized f32 operands
            + hq * dh * 4                   # q tile
            + (2 * hq + hq * dh) * 4)       # m, l, acc carries


def _paged_decode_kernel(pages_ref, pos_ref, layer_ref, q_ref, *refs,
                         page_size, bkp, scale, kv_int8, kv_scale):
    del layer_ref                                      # read by the index maps
    k_refs, v_refs = refs[:bkp], refs[bkp:2 * bkp]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * bkp:]
    b, jb = pl.program_id(0), pl.program_id(1)

    @pl.when(jb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    q = q_ref[0]                                       # (hk, g, dh)
    for i in range(bkp):
        start = (jb * bkp + i) * page_size

        # early page-loop bound: a page whose first token is past the
        # slot's write position holds no valid KV — skip the math
        @pl.when(start <= pos)
        def _page(i=i, start=start):
            k, v = k_refs[i][0], v_refs[i][0]          # (page_size, hk, dh)
            if kv_int8:
                # in-register dequant: the _kv_dequant algebra, static scale
                k = (k.astype(jnp.float32) * kv_scale).astype(q.dtype)
                v = (v.astype(jnp.float32) * kv_scale).astype(q.dtype)
            else:
                k, v = k.astype(q.dtype), v.astype(q.dtype)
            s = jnp.einsum("hgd,thd->hgt", q, k,
                           preferred_element_type=jnp.float32) * scale
            tok = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(tok <= pos, s, NEG_INF)

            m_prev, l_prev = m_ref[...], l_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
                "hgt,thd->hgd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

    @pl.when(jb == pl.num_programs(1) - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "kv_scale",
                                             "interpret"))
def paged_flash_decode(q: jnp.ndarray, k_pool: jnp.ndarray,
                       v_pool: jnp.ndarray, pages: jnp.ndarray,
                       pos: jnp.ndarray, layer: jnp.ndarray | None = None, *,
                       pages_per_block: int | None = None,
                       kv_scale: float = 0.05,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Single-token decode attention through the page-table indirection.

    q: (B, Hq, dh) compute dtype; k_pool/v_pool: (num_pages, page_size, Hk,
    dh) pool dtype (int8 codes at `kv_scale`, or the compute dtype), or a
    stacked (L, num_pages, page_size, Hk, dh) pool read at scalar int32
    `layer`; pages: (B, max_pages) int32 page table (NULL/unallocated
    entries point at the scratch page 0); pos: (B,) int32 per-slot positions
    — the new token's KV must ALREADY be written at pages[b, pos[b]//P]
    offset pos[b]%P (the caller owns the write, same as the gather path).
    Returns (B, Hq, dh).

    `pages_per_block` (Tile.bkq of the "paged_attn/decode/*" TuneTable
    entry; clamped to a divisor of max_pages) sets how many pages one grid
    step DMAs and folds into the online-softmax carries.
    """
    if layer is None:
        assert k_pool.ndim == 4, k_pool.shape
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    b, hq, dh = q.shape
    _, num_pages, page_size, hk, dh_k = k_pool.shape
    assert dh == dh_k and v_pool.shape == k_pool.shape
    assert hq % hk == 0, (hq, hk)
    max_pages = pages.shape[1]
    assert pages.shape == (b, max_pages) and pos.shape == (b,)
    if pages_per_block is None:
        pages_per_block = resolve_pages_per_block()
    bkp = fit_block(pages_per_block, max_pages)
    grid = (b, max_pages // bkp)
    kv_int8 = k_pool.dtype == jnp.int8
    if interpret is None:
        from .dispatch import pallas_interpret
        interpret = pallas_interpret()

    # GQA: query heads grouped (Hk, G, dh) outside the kernel (a free XLA
    # reshape) so the kernel never reshapes a head_dim-96 tile
    g = hq // hk
    blk = pl.BlockSpec((1, hk, g, dh),
                       lambda bi, j, pages, pos, layer: (bi, 0, 0, 0))

    def page_spec(i):
        def index_map(bi, j, pages, pos, layer):
            col = jnp.minimum(j * bkp + i, pos[bi] // page_size)
            return layer[0], pages[bi, col], 0, 0, 0
        return pl.BlockSpec((None, 1, page_size, hk, dh), index_map)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[blk] + [page_spec(i) for i in range(bkp)] * 2,
        out_specs=blk,
        scratch_shapes=[
            pltpu.VMEM((hk, g, 1), jnp.float32),      # m: running max
            pltpu.VMEM((hk, g, 1), jnp.float32),      # l: running denominator
            pltpu.VMEM((hk, g, dh), jnp.float32),     # acc: running output
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, page_size=page_size, bkp=bkp,
        scale=1.0 / dh ** 0.5, kv_int8=kv_int8, kv_scale=kv_scale)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, dh), q.dtype),
        interpret=interpret,
    )(pages.astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(b, hk, g, dh), *[k_pool] * bkp, *[v_pool] * bkp)
    return out.reshape(b, hq, dh)
