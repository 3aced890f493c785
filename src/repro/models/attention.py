"""GQA attention: full/causal/sliding-window, blockwise (flash-style) prefill,
KV-cache decode (linear + ring-buffer), optional cross-attention.

The QKV/O projections are `QuantizedLinear`s — in BrainTTA terms these are the
vMAC GEMMs; the softmax/AV math stays wide (bf16/f32), mirroring the SoC's
wide accumulator path. Blockwise attention keeps the (Tq × Tk) score matrix
tiled (q_block × kv_block), which is mandatory at 32k+ context.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.precision import PrecisionPolicy
from repro.kernels.dispatch import pallas_interpret, round_to

from . import common
from .common import ModelCtx

NEG_INF = -1e30
KV_SCALE = 0.05   # static requant scale for the int8 KV cache (§Perf C)


def _kv_quant(t, dtype):
    """Requantize K/V for cache storage (paper §IV-A requantization applied
    to the cache): int8 codes at a static scale, or passthrough cast."""
    if dtype == jnp.int8:
        return jnp.clip(jnp.round(t.astype(jnp.float32) / KV_SCALE),
                        -127, 127).astype(jnp.int8)
    return t.astype(dtype)


def _kv_dequant(c, compute_dtype):
    if c.dtype == jnp.int8:
        return (c.astype(jnp.float32) * KV_SCALE).astype(compute_dtype)
    return c.astype(compute_dtype)


def _per_head_shard(ctx: ModelCtx, cfg: ArchConfig, kernel, head_axes,
                    out_axis):
    """`kernel` as it runs under serve TP: XLA cannot partition a Mosaic
    kernel, so it runs inside shard_map. When query and kv heads both split
    evenly over the model axis each shard runs its own heads (GQA groups
    stay whole per shard: both split over the same axis in the same order);
    otherwise every shard runs all heads on replicated inputs.
    head_axes: per argument, the axis holding heads (None = replicated);
    out_axis: the output's head axis. Every other axis is replicated, so on
    a mesh with data > 1 each shard gathers the whole KV pool and computes
    every slot (docs/SERVING.md, Known constraints). Without a mesh the
    kernel runs as is."""
    if ctx.tp is None:
        return kernel
    ns = ctx.tp.size
    split = cfg.n_heads % ns == 0 and cfg.n_kv_heads % ns == 0
    ax = ctx.tp.axis if split else None

    def spec(axis, ndim):
        dims = [None] * ndim
        if axis is not None:
            dims[axis] = ax
        return P(*dims)

    def run(*args):
        in_specs = tuple(spec(a, x.ndim) for a, x in zip(head_axes, args))
        out_ndim = args[0].ndim
        return jax.shard_map(kernel, mesh=ctx.tp.mesh, in_specs=in_specs,
                             out_specs=spec(out_axis, out_ndim),
                             check_vma=False)(*args)
    return run


@dataclasses.dataclass(frozen=True)
class AttnSpecs:
    qkv: Any
    out: Any
    cross_q: Any = None
    cross_kv: Any = None


def attn_specs(cfg: ArchConfig, pol: PrecisionPolicy, *, first=False, last=False,
               cross: bool = False) -> AttnSpecs:
    # Megatron pairing for serve TP: qkv is column-parallel (head/out dim
    # sharded, no collective), the out projection is row-parallel (packed-K
    # sharded, one pre-requant psum) — so each attention block costs exactly
    # one TP collective. Only active when a serve mesh threads ctx.tp.
    h, hk, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    mk = lambda lc, i, o, bias=False, par="none": common.lspec(
        pol, lc, i, o, first=first, last=last, bias=bias, parallel=par)
    return AttnSpecs(
        qkv=mk("attn_qkv", d, (h + 2 * hk) * dh, bias=cfg.qkv_bias,
               par="column"),
        out=mk("attn_out", h * dh, d, par="row"),
        cross_q=mk("attn_qkv", d, h * dh, par="column") if cross else None,
        cross_kv=mk("attn_qkv", d, 2 * hk * dh, par="column") if cross else None,
    )


def attn_init(rng, cfg: ArchConfig, specs: AttnSpecs, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    p = {"qkv": common.linear_init(ks[0], specs.qkv, dtype),
         "out": common.linear_init(ks[1], specs.out, dtype)}
    if specs.cross_q is not None:
        p["cross_q"] = common.linear_init(ks[2], specs.cross_q, dtype)
        p["cross_kv"] = common.linear_init(ks[3], specs.cross_kv, dtype)
    return p


def _split_qkv(y: jnp.ndarray, cfg: ArchConfig):
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, t, _ = y.shape
    q, k, v = jnp.split(y, [h * dh, (h + hk) * dh], axis=-1)
    return (q.reshape(b, t, h, dh), k.reshape(b, t, hk, dh), v.reshape(b, t, hk, dh))


def _gqa_scores_blockless(q, k, v, mask):
    """Reference small-scale attention. q: (B,Tq,H,dh) k/v: (B,Tk,Hk,dh)."""
    b, tq, h, dh = q.shape
    hk = k.shape[2]
    g = h // hk
    qg = q.reshape(b, tq, hk, g, dh)
    s = jnp.einsum("bthgd,bshd->bhgts", qg, k).astype(jnp.float32) / dh ** 0.5
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgts,bshd->bthgd", a, v)
    return o.reshape(b, tq, h, dh)


def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        q_block: int = 512, kv_block: int = 1024,
                        q_offset=0, cp: bool = False) -> jnp.ndarray:
    """Flash-style blocked attention with online softmax.

    q: (B, Tq, H, dh); k,v: (B, Tk, Hk, dh). `window`>0 restricts each query
    to the last `window` keys (sliding-window / local attention), which also
    shrinks the kv loop to the band — sub-quadratic in T.
    `q_offset`: absolute position of q[0] (prefill continuation / decode).

    Scores and the PV products accumulate in f32, as in the flash and
    paged-decode kernels: rounding them to bf16 first made this path drift
    from the kernels by tens of percent in logits over a deep stack. The
    softmax weights and the output round to bf16 where the flash kernel
    rounds them (`dispatch.round_to`).

    Two schedules:
      cp=False  two-level scan (q blocks x kv blocks) — bounds the score temp
                to (B, qb, H, kvb); used on host-scale runs and window layers.
      cp=True   context-parallel: the caller sharded Tq over the model axis,
                so the score temp is already bounded by the T shard; a single
                kv scan keeps every tensor's Tq dim intact (reshapes that
                split a sharded dim break GSPMD propagation — measured 2.4x
                compute + 200 GiB gather churn; see EXPERIMENTS.md §Perf).
    """
    b, tq, h, dh = q.shape
    tk, hk = k.shape[1], k.shape[2]
    g = h // hk
    q_block = min(q_block, tq)
    kv_block = min(kv_block, tk)
    if tq % q_block or tk % kv_block:           # fallback for odd smoke shapes
        mask = jnp.ones((b, tq, tk), bool)
        pos_q = jnp.arange(tq) + q_offset
        pos_k = jnp.arange(tk)
        if causal:
            mask &= pos_q[None, :, None] >= pos_k[None, None, :]
        if window:
            mask &= pos_q[None, :, None] - pos_k[None, None, :] < window
        return _gqa_scores_blockless(q, k, v, mask)

    nq, nk = tq // q_block, tk // kv_block
    scale = 1.0 / dh ** 0.5

    # how many kv blocks each q block needs to visit
    if window:
        band = window + q_block                     # kv span per q block
        nkv_visit = min(nk, (band + kv_block - 1) // kv_block + 1)
    else:
        nkv_visit = nk

    def kv_scan(qi_blk, q_pos, start_blk, n_visit):
        """Online-softmax sweep of kv blocks for one q block.
        qi_blk: (B, Tq', Hk, G, dh)."""
        tq_ = qi_blk.shape[1]

        def kv_step(carry, j):
            m, l, acc = carry
            kj = start_blk + j
            ks = jax.lax.dynamic_slice_in_dim(k, kj * kv_block, kv_block, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, kj * kv_block, kv_block, axis=1)
            s = jnp.einsum("bqhgd,bshd->bhgqs", qi_blk, ks,
                           preferred_element_type=jnp.float32) * scale
            k_pos = kj * kv_block + jnp.arange(kv_block)
            msk = jnp.ones((tq_, kv_block), bool)
            if causal:
                msk &= q_pos[:, None] >= k_pos[None, :]
            if window:
                msk &= q_pos[:, None] - k_pos[None, :] < window
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqs,bshd->bhgqd", round_to(p, q.dtype), vs,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hk, g, tq_), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hk, g, tq_), jnp.float32)
        a0 = jnp.zeros((b, hk, g, tq_, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(n_visit))
        o = acc / jnp.maximum(l, 1e-20)[..., None]
        return round_to(o, q.dtype)                  # (B, Hk, G, Tq', dh)

    if cp:
        # single-level: whole (sequence-sharded) q against the kv sweep
        qg = q.reshape(b, tq, hk, g, dh)
        o = kv_scan(qg, q_offset + jnp.arange(tq), 0, nk)
        o = jnp.moveaxis(o, 3, 1)                    # (B, Tq, Hk, G, dh)
        return o.reshape(b, tq, h, dh)

    qb = q.reshape(b, nq, q_block, hk, g, dh)

    def q_step(_, qi):
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)
        if window:
            start_blk = jnp.clip((q_offset + qi * q_block - window) // kv_block,
                                 0, nk - nkv_visit)
        else:
            start_blk = 0
        return None, kv_scan(qb[:, qi], q_pos, start_blk, nkv_visit)

    _, ob = jax.lax.scan(q_step, None, jnp.arange(nq))  # (nq, B, Hk, G, qblk, dh)
    o = jnp.moveaxis(ob, 0, 1)                            # (B, nq, Hk, G, qblk, dh)
    o = jnp.moveaxis(o, -2, 2)                            # (B, nq, qblk, Hk, G, dh)
    return o.reshape(b, tq, h, dh)


# ---------------------------------------------------------------------------
# block-level apply: prefill/train, decode, cross-attention
# ---------------------------------------------------------------------------

def attn_apply(p, x, specs: AttnSpecs, cfg: ArchConfig, ctx: ModelCtx, *,
               causal: bool = True, window: int = 0, positions=None,
               return_cache: bool = False, cache_len: int = 0):
    """Full-sequence attention (train / prefill). x: (B, T, D).

    With return_cache: the KV cache is laid out for `attn_decode` —
    full-attention layers get `cache_len` (>= T) linear slots; window layers
    get a ring buffer of capacity min(window, cache_len) where position p
    lives at slot p % capacity.
    """
    b, t, _ = x.shape
    y = common.linear_apply(p["qkv"], x, specs.qkv, ctx)
    q, k, v = _split_qkv(y, cfg)
    if positions is None:
        positions = jnp.arange(t)
    q = common.rope(q, positions, cfg.rope_theta)
    k = common.rope(k, positions, cfg.rope_theta)
    # serve TP: heads arrive model-sharded from the column-parallel qkv
    # shard_map (its out_specs put the fused head dim on the model axis) and
    # GSPMD propagates that through split/rope into the per-head score/AV
    # einsums. Do NOT re-pin the head axis with an explicit
    # with_sharding_constraint here: on the CPU SPMD backend that constraint
    # miscompiles the blocked-attention scan (wrong values, not just layout
    # churn) — the serving TP oracle in tests/test_serving_tp.py catches it.
    if (ctx.backend == "pallas" and not window and t % 256 == 0
            and ctx.attn_cp is None):
        # TPU deployment path: fused flash-attention kernel (kernels/flash_attn)
        from repro.kernels.flash_attn import flash_attention as _flash
        b_, t_, h_, dh_ = q.shape
        hk_ = k.shape[2]
        qf = jnp.moveaxis(q, 2, 1).reshape(b_ * h_, t_, dh_)
        kf = jnp.moveaxis(k, 2, 1).reshape(b_ * hk_, t_, dh_)
        vf = jnp.moveaxis(v, 2, 1).reshape(b_ * hk_, t_, dh_)
        flash = functools.partial(_flash, causal=causal,
                                  interpret=pallas_interpret())
        of = _per_head_shard(ctx, cfg, flash, (0, 0, 0), 0)(qf, kf, vf)
        o = jnp.moveaxis(of.reshape(b_, h_, t_, dh_), 1, 2)
        out = common.linear_apply(p["out"], o.reshape(b, t, -1), specs.out, ctx)
        if return_cache:
            cap = max(cache_len or t, 1)
            if t < cap:
                pad = ((0, 0), (0, cap - t), (0, 0), (0, 0))
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            cd = jnp.int8 if cfg.kv_cache_dtype == "int8" else k.dtype
            return out, {"k": _kv_quant(k, cd), "v": _kv_quant(v, cd)}
        return out

    cp = bool(ctx.attn_cp) and not window and t % 512 == 0
    if cp:
        # context parallelism: q sequence sharded over the model axis, kv
        # replicated within the dp group — head-count agnostic (llama 24H/8KV
        # doesn't divide a 16-way model axis; head-TP would pad & churn).
        q = common.shard_spec(q, ctx, ctx.attn_cp, None, None)
        k = common.shard_spec(k, ctx, None, None, None)
        v = common.shard_spec(v, ctx, None, None, None)
    elif ctx.attn_cp and window:
        # window layers: cheap (banded) — replicate over model inside the dp
        # group rather than churn on reshapes; see DESIGN.md §Perf notes
        q = common.shard_spec(q, ctx, None, None, None)
        k = common.shard_spec(k, ctx, None, None, None)
        v = common.shard_spec(v, ctx, None, None, None)
    o = blockwise_attention(q, k, v, causal=causal, window=window, cp=cp)
    if cp:
        o = common.shard_spec(o, ctx, ctx.attn_cp, None, None)
    out = common.linear_apply(p["out"], o.reshape(b, t, -1), specs.out, ctx)
    if return_cache:
        cap = max(cache_len or t, 1)
        if window:
            cap = min(window, cap)
        if t > cap:
            k, v = k[:, -cap:], v[:, -cap:]
            if window:                      # ring alignment: slot = pos % cap
                k = jnp.roll(k, t % cap, axis=1)
                v = jnp.roll(v, t % cap, axis=1)
        elif t < cap:
            pad = ((0, 0), (0, cap - t), (0, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        # int8 cache when requested; otherwise the cache follows the compute
        # dtype (so f32 verification runs stay exact)
        cd = jnp.int8 if cfg.kv_cache_dtype == "int8" else k.dtype
        return out, {"k": _kv_quant(k, cd), "v": _kv_quant(v, cd)}
    return out


def init_cache_shapes(cfg: ArchConfig, batch: int, seq_len: int, window: int,
                      dtype=None, paged: tuple[int, int] | None = None):
    """Cache ShapeDtypeStructs for one attention layer.

    `paged=(num_pages, page_size)` switches full-attention layers to the
    block-pool layout (num_pages, page_size, Hk, dh) shared by every slot via
    a page table (launch/kv_cache.py). Window layers keep their per-slot ring
    buffers — the ring is already bounded at `window` tokens, so paging it
    buys nothing.
    """
    if dtype is None:
        dtype = jnp.dtype(cfg.kv_cache_dtype)
    if paged is not None and not window:
        num_pages, page_size = paged
        shp = (num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    else:
        s = min(window, seq_len) if window else seq_len
        shp = (batch, s, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shp, dtype),
            "v": jax.ShapeDtypeStruct(shp, dtype)}


def attn_decode(p, x, cache, pos, specs: AttnSpecs, cfg: ArchConfig,
                ctx: ModelCtx, *, window: int = 0, pages=None, layer=None):
    """One-token decode. x: (B, 1, D); pos: scalar or per-row (B,) int32.

    Per-row positions drive RoPE phases, the cache-write index, and the
    validity mask independently per slot (continuous batching: slots decode
    at unrelated positions).

    Layouts:
      contiguous  cache k/v: (B, S|W, Hk, dh); full attention writes at
                  index pos[b], window layers ring-write at pos[b] % W.
      paged       cache k/v: (num_pages, page_size, Hk, dh) + `pages`
                  (B, max_pages) page table; writes go to
                  pages[b, pos[b]//P] at offset pos[b] % P, reads gather the
                  row's page list back into a (B, max_pages*P, Hk, dh) view.
                  Unallocated table entries point at page 0 (scratch); reads
                  from it are masked by `valid`, writes to it are discarded
                  garbage by construction.
      stacked     paged with `layer` (scalar int32): cache k/v are the
                  (L, num_pages, page_size, Hk, dh) pools of every layer of
                  a scan; the write lands at [layer, pid, off] and both read
                  paths read that layer of the whole pool. Nothing slices
                  the layer out, so under a scan that carries the pool the
                  update is in place (transformer.decode_step).

    Prefix sharing contract: one physical page may appear in SEVERAL rows of
    `pages` (requests aliasing a common prompt prefix) — the gather-based
    read path is oblivious to that. The write below is only safe because the
    scheduler forks shared pages BEFORE handing the table to this step
    (copy-on-write in launch/serve.py `_prepare_pages` via
    kv_cache.fork_cow + copy_page): by contract, `pages[b, pos[b]//P]` is
    exclusively owned by row b whenever row b is active. Do not add writes
    through `pages` anywhere else without routing them past that fork.

    Paged read paths (ctx.paged_attn; docs/SERVING.md §Paged-attention
    decode kernel): the jnp gather path above is the oracle; with
    backend="pallas" (or paged_attn="fused") the read side instead runs
    `kernels.paged_attn.paged_flash_decode`, which walks the SAME post-fork
    table page by page inside the kernel (scalar-prefetched `pages`/`pos`,
    per-page DMA + online softmax) — the write side below is shared by both,
    so the CoW contract is path-independent. When `pos` is concrete (eager
    oracle/bench callers — under the server's jit it is a tracer and the
    table width is part of the fixed decode signature), the table is first
    sliced to max(pos)//P + 1 columns so neither path touches dead pages.
    """
    b = x.shape[0]
    y = common.linear_apply(p["qkv"], x, specs.qkv, ctx)
    q, k_new, v_new = _split_qkv(y, cfg)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))       # (B,)
    posv = posb[:, None]
    q = common.rope(q, posv, cfg.rope_theta)
    k_new = common.rope(k_new, posv, cfg.rope_theta)
    # serve TP: head-sharded decode falls out of the column-parallel qkv
    # shard_map out_specs (see attn_apply — no explicit head re-pin here)

    cd = cache["k"].dtype
    kq, vq = _kv_quant(k_new, cd)[:, 0], _kv_quant(v_new, cd)[:, 0]  # (B,Hk,dh)
    rows = jnp.arange(b)
    if pages is not None and not window:
        page_size = cache["k"].shape[-3]
        if not isinstance(posb, jax.core.Tracer):
            # eager caller (oracle tests / benches): length-bound the table
            # to the last active page — dead pages past max(pos) are neither
            # gathered/dequantized nor walked by the kernel. Under jit `pos`
            # is a tracer and the full (fixed-signature) width stays.
            pages = pages[:, :int(jnp.max(posb)) // page_size + 1]
        pid = pages[rows, posb // page_size]
        off = posb % page_size
        at = (pid, off) if layer is None else (layer, pid, off)
        k = cache["k"].at[at].set(kq)
        v = cache["v"].at[at].set(vq)
        fused = (ctx.paged_attn == "fused"
                 or (ctx.paged_attn == "auto" and ctx.backend == "pallas"))
        if fused:
            # fused page-walk kernel (reads the same post-fork table and the
            # post-write pool, so CoW/write semantics match the gather path)
            from repro.kernels import paged_attn as _pa
            h_, hk_, dh_ = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
            walk = functools.partial(
                _pa.paged_flash_decode,
                pages_per_block=_pa.resolve_pages_per_block(ctx.tune),
                kv_scale=KV_SCALE, interpret=pallas_interpret())
            args = (q[:, 0], k, v, pages, posb)
            if layer is not None:
                args += (jnp.asarray(layer, jnp.int32),)
            hax = k.ndim - 2                            # the pool's head axis
            of = _per_head_shard(
                ctx, cfg, walk, (1, hax, hax, None, None, None)[:len(args)],
                1)(*args)
            out = common.linear_apply(p["out"], of.reshape(b, 1, h_ * dh_),
                                      specs.out, ctx)
            return out, {"k": k, "v": v}
        s = pages.shape[1] * page_size
        at = pages if layer is None else (layer, pages)
        kf = _kv_dequant(k[at].reshape(b, s, *k.shape[-2:]), x.dtype)
        vf = _kv_dequant(v[at].reshape(b, s, *v.shape[-2:]), x.dtype)
        valid = jnp.arange(s)[None, :] <= posb[:, None]               # (B, S)
    else:
        s = cache["k"].shape[1]
        idx = (posb % s) if window else jnp.minimum(posb, s - 1)      # (B,)
        k = cache["k"].at[rows, idx].set(kq)
        v = cache["v"].at[rows, idx].set(vq)
        kf, vf = _kv_dequant(k, x.dtype), _kv_dequant(v, x.dtype)
        slots = jnp.arange(s)
        if window:
            # ring full => every slot valid
            valid = (slots[None, :] <= idx[:, None]) | (posv >= s)
        else:
            valid = slots[None, :] <= posv

    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hk
    qg = q.reshape(b, hk, g, dh)
    sc = jnp.einsum("bhgd,bshd->bhgs", qg, kf).astype(jnp.float32) / dh ** 0.5
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    a = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhgs,bshd->bhgd", a, vf).reshape(b, 1, h * dh)
    out = common.linear_apply(p["out"], o, specs.out, ctx)
    return out, {"k": k, "v": v}


def attn_prefill_chunk(p, x, cache, pos0, specs: AttnSpecs, cfg: ArchConfig,
                       ctx: ModelCtx, *, read_pages, write_pages, nreal):
    """Prefill one prompt *chunk* against the paged cache at a position offset.

    x: (B, C, D) — C chunk tokens (right-padded past `nreal`); pos0: (B,)
    absolute position of the chunk's first token; read_pages/write_pages:
    (B, max_pages) page rows. Token t sits at absolute position pos0+t, its
    KV is scattered to write_pages[(pos0+t)//P] offset (pos0+t)%P, and its
    query attends every pooled token at position <= pos0+t — the already-
    written prefix chunks plus this chunk's own tokens (scatter happens
    before the gather, so in-chunk causal attention reads through the pool).

    Two page rows because prefix sharing masks WRITES, not reads: a shared
    page already holds this prefix's KV (bytes are a pure function of the
    token prefix), so its write_pages entry is NULL_PAGE (scratch) while
    read_pages keeps the real id. Padding tokens (t >= nreal) are likewise
    redirected to scratch.

    Byte-exactness contract (tests/test_serving.py): this path must produce
    bit-identical KV to whole-prompt `attn_apply` prefill. It therefore
    mirrors `_gqa_scores_blockless` exactly — same einsum contractions, same
    masked softmax — relying on two XLA-CPU invariances the serving oracles
    already lean on: row-slicing a matmul and padding a masked key axis are
    both bit-exact. Requires the pool dtype == compute dtype (the int8 KV
    cache re-quantizes at chunk boundaries, which whole-prompt prefill does
    not — the server disables chunking there).
    """
    b, c, _ = x.shape
    y = common.linear_apply(p["qkv"], x, specs.qkv, ctx)
    q, k_new, v_new = _split_qkv(y, cfg)
    positions = (jnp.asarray(pos0, jnp.int32)[:, None]
                 + jnp.arange(c, dtype=jnp.int32)[None, :])          # (B, C)
    q = common.rope(q, positions, cfg.rope_theta)
    k_new = common.rope(k_new, positions, cfg.rope_theta)

    cd = cache["k"].dtype
    kq, vq = _kv_quant(k_new, cd), _kv_quant(v_new, cd)              # (B,C,Hk,dh)
    page_size = cache["k"].shape[1]
    rows = jnp.arange(b)
    tvalid = jnp.arange(c)[None, :] < jnp.asarray(nreal, jnp.int32)[:, None]
    pidx = jnp.minimum(positions // page_size, write_pages.shape[1] - 1)
    pid = jnp.where(tvalid, write_pages[rows[:, None], pidx], 0)     # NULL_PAGE
    off = positions % page_size
    k = cache["k"].at[pid, off].set(kq)
    v = cache["v"].at[pid, off].set(vq)

    s = read_pages.shape[1] * page_size
    kf = _kv_dequant(k[read_pages].reshape(b, s, *k.shape[2:]), x.dtype)
    vf = _kv_dequant(v[read_pages].reshape(b, s, *v.shape[2:]), x.dtype)
    valid = jnp.arange(s)[None, None, :] <= positions[:, :, None]    # (B, C, S)

    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hk
    qg = q.reshape(b, c, hk, g, dh)
    sc = jnp.einsum("bthgd,bshd->bhgts", qg, kf).astype(jnp.float32) / dh ** 0.5
    sc = jnp.where(valid[:, None, None, :, :], sc, NEG_INF)
    a = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhgts,bshd->bthgd", a, vf).reshape(b, c, h * dh)
    out = common.linear_apply(p["out"], o, specs.out, ctx)
    return out, {"k": k, "v": v}


# -- cross attention (whisper decoder) ----------------------------------------

def cross_attn_apply(p, x, enc_kv, specs: AttnSpecs, cfg: ArchConfig, ctx: ModelCtx):
    """x: (B, T, D); enc_kv: precomputed (k, v) from the encoder output."""
    b, t, _ = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = common.linear_apply(p["cross_q"], x, specs.cross_q, ctx).reshape(b, t, h, dh)
    k, v = enc_kv
    o = blockwise_attention(q, k, v, causal=False)
    return common.linear_apply(p["out"], o.reshape(b, t, -1), specs.out, ctx)


def cross_kv(p, enc_out, specs: AttnSpecs, cfg: ArchConfig, ctx: ModelCtx):
    b, s, _ = enc_out.shape
    hk, dh = cfg.n_kv_heads, cfg.head_dim
    kv = common.linear_apply(p["cross_kv"], enc_out, specs.cross_kv, ctx)
    k, v = jnp.split(kv, 2, axis=-1)
    return k.reshape(b, s, hk, dh), v.reshape(b, s, hk, dh)
