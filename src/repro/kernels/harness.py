"""Generic output-stationary tiled GEMM harness — one Pallas skeleton, many
precisions.

BrainTTA's point is a single flexible datapath that serves binary, ternary and
int8 operands through the same machine (§III). The TPU translation of that is
this module: ONE pallas_call scaffold — grid (M/bm, N/bn, Kq/bkq) with K
innermost, int32 accumulator tiles held in VMEM scratch across the K sweep,
and the requantization epilogue (w_scale[n] * a_scale[m] + bias[n], §IV-B
"as early as possible") fused behind the MAC on the last K step — and a
`MacBody` per precision that supplies ONLY the inner MAC computation
(xnor-popcount, gated-xnor, int8-dot, mxu-unpack).

`repro.kernels.{bgemm,tgemm,i8gemm}` shrink to MacBody definitions; the
precision registry in `repro.kernels.dispatch` maps (wprec, aprec, impl)
operating points onto bodies. Adding a kernel variant = one MacBody + one
registry entry; the grid/BlockSpec/scratch/epilogue machinery below is never
copied again.

Kq is the *storage* K axis: K/32 packed words for the bit-plane formats
(body.k_per_q = 32), K int8 codes for the 8-bit format (body.k_per_q = 1),
K/8 nibble words for s4. Mixed w/a precisions give the two operand sides
different densities (xk_per_q / wk_per_q); the grid quantum is their lcm so
every K step covers whole storage units of both. Block shapes are a `Tile`
(bm, bn, bkq) — the unit the per-cell `dispatch.TuneTable` tunes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@dataclasses.dataclass(frozen=True)
class Tile:
    """One kernel block-shape choice: the tunable of an operating point.

    bm/bn block the output tile; bkq blocks the K sweep in units of the
    body's grid quantum `k_per_q` (packed words for the bit-plane formats,
    elements for int8). None bkq = the body's default. Carried on
    `dispatch.OperatingPoint` (explicit override) or resolved from a
    `dispatch.TuneTable` (per-cell autotune data)."""
    bm: int = 128
    bn: int = 128
    bkq: int | None = None


@dataclasses.dataclass(frozen=True)
class MacBody:
    """The per-precision inner MAC of the output-stationary kernel.

    step(xs, ws, accs, *, bkq) -> new accs
        one grid K-step update, bkq in grid-quantum units. xs: n_x
        activation tiles (bm, bkq*k_per_q/xk_per_q);
        ws: n_w weight tiles ((bn, bkq*k_per_q/wk_per_q) or transposed per
        w_kmajor); accs: n_acc int32 (bm, bn) accumulator values.
    finish(accs, k_total) -> (bm, bn) int32/f32 dot
        maps the raw accumulators to the integer dot product (e.g. the
        XNOR identity K - 2*mismatches) right before requantization.

    Activation and weight operands may use DIFFERENT storage densities
    (mixed w/a precision, e.g. ternary planes × int8 codes): xk_per_q /
    wk_per_q give each side's K elements per storage unit (None =>
    k_per_q). k_per_q is the grid quantum — the lcm of the two sides — so
    one K grid step always covers whole storage units of both operands.
    """
    name: str
    n_x: int                 # activation operand arrays, each (M, Kq_x)
    n_w: int                 # weight operand arrays
    n_acc: int               # int32 VMEM accumulator tiles
    k_per_q: int             # K elements per grid-K unit (coarsest operand)
    step: Callable
    finish: Callable
    w_kmajor: bool = False   # True: weights are (Kq, N) (int8 codes layout)
    unpacks_f32: bool = False  # step materializes f32 (R, bkq*k_per_q)
                               # unpacked operand tiles in VMEM (MXU bodies)
    unpacks_i8: bool = False   # step materializes int8 unpacked weight tiles
    default_bkq: int = 16
    xk_per_q: int | None = None  # activation storage density (None = k_per_q)
    wk_per_q: int | None = None  # weight storage density (None = k_per_q)
    w_ref: bool = False      # step gets the weight refs, not loaded tiles
                             # (it loads slices itself, e.g. one plane)
    w_stack: int = 0         # >0: weight operands carry a leading stacked
                             # plane axis — (planes, N, Kq) — swept whole per
                             # grid step (plane-composed cells). The value is
                             # the FULL stack depth (the vmem model's worst
                             # case); the live depth is the operand's shape[0]
                             # (a truncated stack just traces a smaller tile).

    @property
    def xk(self) -> int:
        return self.xk_per_q or self.k_per_q

    @property
    def wk(self) -> int:
        return self.wk_per_q or self.k_per_q


def requant(dot, w_scale, a_scale, bias):
    """The fused requant epilogue, defined once for every backend.

    out = dot * w_scale[n] * a_scale[m] + bias[n], computed in f32 so the
    wide accumulator never round-trips through a narrow dtype (§IV-B). Any
    scale/bias may be None (identity). w_scale/bias are (N,) or (1, N) and
    a_scale (M,) or (M, 1) — the kernel reads 2-D tiles, the jnp backend
    1-D vectors; the arithmetic is the same. Callers cast the result.
    """
    y = dot.astype(jnp.float32)
    if w_scale is not None:
        y = y * w_scale.reshape(1, -1)
    if a_scale is not None:
        y = y * a_scale.reshape(-1, 1)
    if bias is not None:
        y = y + bias.reshape(1, -1)
    return y


def _kernel(*refs, body: MacBody, k_total: int, bkq: int, acc_only: bool):
    """One (bm, bn) output tile; grid dim 2 sweeps Kq (output-stationary)."""
    nx, nw = body.n_x, body.n_w
    x_tiles = tuple(refs[i][...] for i in range(nx))
    w_tiles = tuple(refs[nx + i] if body.w_ref else refs[nx + i][...]
                    for i in range(nw))
    ws_ref, as_ref, b_ref = refs[nx + nw:nx + nw + 3]
    o_ref = refs[nx + nw + 3]
    acc_refs = refs[nx + nw + 4:]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        for a in acc_refs:
            a[...] = jnp.zeros_like(a)

    new_accs = body.step(x_tiles, w_tiles,
                         tuple(a[...] for a in acc_refs), bkq=bkq)
    for a, v in zip(acc_refs, new_accs):
        a[...] = v

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        dot = body.finish(tuple(a[...] for a in acc_refs), k_total)
        if acc_only:
            # tensor-parallel row shard: emit the raw integer dot so the
            # caller can psum partial sums across K shards BEFORE requant
            # (requantizing per-shard partials is numerically wrong)
            o_ref[...] = dot.astype(jnp.int32)
        else:
            y = requant(dot, ws_ref[...], as_ref[...], b_ref[...])
            o_ref[...] = y.astype(o_ref.dtype)


#: TPU vreg tiling: a block's last dim must be a multiple of LANE (or the
#: whole array dim), its second-to-last a multiple of SUBLANE (or whole).
LANE, SUBLANE = 128, 8

#: scoped-VMEM limit for the qgemm kernels. Mosaic's 16 MiB default is too
#: small for the unpacking bodies: a packed K block is at least 128 words
#: (4,096 elements) unless it is the whole K, and its unpacked (bn, 4096)
#: operand tiles alone take several MiB. v5e has 128 MiB of VMEM per core.
VMEM_LIMIT = 64 * 2 ** 20


def k_block(body: MacBody, requested: int, units: int) -> int:
    """The K block (grid quanta per step) for `body` over `units` quanta.

    A divisor of `units` whose storage width on BOTH operand sides
    (bkq*k_per_q/xk and bkq*k_per_q/wk) is a lane multiple, or the whole K —
    the only K blocks Mosaic accepts. The largest such block <= requested,
    else the smallest one above it (the whole K always qualifies)."""
    q = body.k_per_q

    def ok(b):
        return b == units or all((b * q // side) % LANE == 0
                                 for side in (body.xk, body.wk))

    fits = [b for b in range(1, units + 1) if units % b == 0 and ok(b)]
    below = [b for b in fits if b <= requested]
    return max(below) if below else min(fits)


def fit_block(requested: int, dim: int, align: int = 1) -> int:
    """Largest block <= requested that divides dim exactly, preferring
    multiples of `align` (TPU sublane alignment for the M block — an
    unaligned int32 accumulator tile won't compile outside interpret mode).
    Falls back to a plain divisor when no aligned one exists."""
    top = max(min(requested, dim), 1)
    for b in range(top, 0, -1):
        if dim % b == 0 and b % align == 0:
            return b
    for b in range(top, 0, -1):
        if dim % b == 0:
            return b
    return 1


@functools.partial(jax.jit, static_argnames=(
    "body", "k", "tile", "interpret", "out"))
def gemm(body: MacBody, x_ops: Sequence[jnp.ndarray], w_ops: Sequence[jnp.ndarray],
         w_scale: jnp.ndarray, a_scale: jnp.ndarray,
         bias: jnp.ndarray | None = None, *, k: int,
         tile: Tile | None = None,
         interpret: bool | None = None, out: str = "requant") -> jnp.ndarray:
    """Run `body` through the shared output-stationary skeleton.

    x_ops: n_x arrays (M, K/xk_per_q); w_ops: n_w arrays (N, K/wk_per_q)
    ((Kq, N) if w_kmajor); w_scale (N,) f32; a_scale (M,) f32; bias (N,) f32
    or None (fused in the epilogue — no separate f32 round-trip)
    -> (M, N) bf16.

    `tile` is the block-shape choice (a `Tile`; None = the body's default).
    `dispatch.qgemm` resolves it from the OperatingPoint's explicit override
    or the per-cell TuneTable before calling here.

    out="acc" skips the requant epilogue and returns the raw (M, N) int32
    dot instead — the row-parallel tensor-parallel path runs the kernel per
    K shard and psums the integer partials across the model axis before the
    (deferred, out-of-kernel) requant. w_scale/a_scale/bias may then be None.

    bm is the largest sublane-aligned divisor of M (callers,
    `dispatch.qgemm`, pad M); bn is a lane multiple over a cdiv grid (ragged
    N needs no padding); the K block comes from `k_block`. interpret=None
    follows the platform (`dispatch.pallas_interpret`).
    """
    if out not in ("requant", "acc"):
        raise ValueError(f"out={out!r}")
    tile = tile or Tile()
    q, xk, wk = body.k_per_q, body.xk, body.wk
    assert q % xk == 0 and q % wk == 0, (body.name, q, xk, wk)
    m = x_ops[0].shape[0]
    if body.w_stack:
        n = w_ops[0].shape[-2]
    else:
        n = w_ops[0].shape[0] if not body.w_kmajor else w_ops[0].shape[1]
    units = k // q                  # grid-quantum count along K
    assert units * q == k, (body.name, k, q)
    for xo in x_ops:
        assert xo.shape == (m, k // xk), (xo.shape, m, k, xk)
    for wo in w_ops:
        if body.w_stack:
            assert wo.ndim == 3 and wo.shape[-2:] == (n, k // wk) \
                and 1 <= wo.shape[0] <= body.w_stack, (wo.shape, n, k, wk)
        else:
            assert wo.shape == ((n, k // wk) if not body.w_kmajor
                                else (k // wk, n)), (wo.shape, n, k, wk)
    bm = fit_block(tile.bm, m, align=SUBLANE)
    # N rides a cdiv grid: a lane-multiple bn (or the whole N) keeps every
    # block Mosaic-aligned at any N (vocab 32,064 is not a multiple of 128).
    # The edge block's out-of-range weight columns only feed output columns
    # that the masked store drops, so no weight padding/copy is needed.
    bn = n if n <= tile.bn else max(LANE, tile.bn // LANE * LANE)
    bkq = k_block(body, tile.bkq if tile.bkq is not None else body.default_bkq,
                  units)
    bx, bw = bkq * q // xk, bkq * q // wk   # per-side block widths (units)
    if out == "acc":
        # scales are unused by the raw-accumulator epilogue; feed dummies so
        # the BlockSpecs stay uniform. In requant mode None scales stay a
        # loud error — substituting zeros would silently zero the output.
        w_scale = jnp.zeros((n,), jnp.float32) if w_scale is None else w_scale
        a_scale = jnp.zeros((m,), jnp.float32) if a_scale is None else a_scale
    if bias is None:
        bias = jnp.zeros((n,), jnp.float32)

    x_spec = pl.BlockSpec((bm, bx), lambda i, j, kk: (i, kk))
    if body.w_stack:
        # stacked plane axis rides whole in every grid step (the plane loop
        # lives inside the MacBody); live depth is the operand's, so a
        # truncated draft stack traces a proportionally smaller tile
        stack = w_ops[0].shape[0]
        w_spec = pl.BlockSpec((stack, bn, bw), lambda i, j, kk: (0, j, kk))
    elif body.w_kmajor:
        w_spec = pl.BlockSpec((bw, bn), lambda i, j, kk: (kk, j))
    else:
        w_spec = pl.BlockSpec((bn, bw), lambda i, j, kk: (j, kk))
    row_n = pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))   # w_scale, bias
    col_m = pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0))   # a_scale
    grid = (m // bm, pl.cdiv(n, bn), units // bkq)
    out_dtype = jnp.int32 if out == "acc" else jnp.bfloat16
    if interpret is None:
        from .dispatch import pallas_interpret
        interpret = pallas_interpret()
    return pl.pallas_call(
        functools.partial(_kernel, body=body, k_total=k, bkq=bkq,
                          acc_only=(out == "acc")),
        grid=grid,
        in_specs=[x_spec] * body.n_x + [w_spec] * body.n_w + [
            row_n, col_m, row_n],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)] * body.n_acc,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*x_ops, *w_ops, w_scale.reshape(1, n), a_scale.reshape(m, 1),
      bias.reshape(1, n))


def gemm_grouped(body: MacBody, x_ops, w_ops, w_scale=None, a_scale=None,
                 bias=None, *, k: int, tile: Tile | None = None,
                 interpret: bool | None = None, out: str = "requant"):
    """Grouped-expert entry point: `gemm` vmapped over a leading group axis.

    Every operand (each x_op, each w_op, and any non-None scale/bias)
    carries the same leading G axis; one Pallas launch runs per group
    member on its own token slab — the segment-GEMM of the expert-parallel
    MoE path (kernels.dispatch._ep_row). None operands stay None (they map
    to `gemm`'s zero dummies), so the (M, N) algebra per group is exactly
    `gemm`'s — grouped-vs-looped equivalence is an identity, not a check.
    """
    ops = {"x": tuple(x_ops), "w": tuple(w_ops)}
    if w_scale is not None:
        ops["ws"] = w_scale
    if a_scale is not None:
        ops["as"] = a_scale
    if bias is not None:
        ops["b"] = bias
    fn = lambda d: gemm(body, d["x"], d["w"], d.get("ws"), d.get("as"),
                        d.get("b"), k=k, tile=tile, interpret=interpret,
                        out=out)
    return jax.vmap(fn)(ops)


def vmem_tile_bytes(body: MacBody, tile: Tile | None = None) -> int:
    """VMEM working set of one grid step (the kernel_bench tile model)."""
    tile = tile or Tile()
    bm, bn = tile.bm, tile.bn
    bkq = tile.bkq if tile.bkq is not None else body.default_bkq
    q = body.k_per_q
    bx, bw = bkq * q // body.xk, bkq * q // body.wk  # per-side storage units
    xb = 4 if body.xk > 1 else 1                     # u32 words vs int8 codes
    wb = 4 if body.wk > 1 else 1
    k_elems = bkq * q
    unpacked = ((body.n_x * bm + body.n_w * bn) * k_elems * 4
                if body.unpacks_f32 else 0)          # f32 ±1/trit operands
    if body.unpacks_i8:
        unpacked += body.n_w * bn * k_elems          # int8 unpacked weights
    stack = body.w_stack or 1                        # full-depth worst case
    return (body.n_x * bm * bx * xb                  # activation tiles
            + body.n_w * bn * bw * wb * stack        # weight tiles (x planes)
            + unpacked                               # MXU-body intermediates
            + body.n_acc * bm * bn * 4               # int32 accumulators
            + bm * bn * 2                            # bf16 out tile
            + (bm + 2 * bn) * 4)                     # scales + bias
