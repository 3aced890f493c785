"""Ternary gated-XNOR MAC bodies — the vTMAC unit.

Trits are stored as two bit-planes (mask, sign) per `repro.core.pack`:
16 trits per 32-bit word-pair (v_C=16, §IV-B). The gated-XNOR algebra
(§II-A): a lane contributes only when both operands are non-zero
(mask AND), the product sign is the XOR of the sign bits:

    active   = xm & wm
    disagree = active & (xs ^ ws)
    dot     += popcount(active) − 2·popcount(disagree)

TERNARY_POPCOUNT keeps two int32 accumulators (active, disagree) and
resolves the dot in finish(); TERNARY_MXU is the beyond-paper variant that
unpacks the trit planes to {-1,0,+1} in VMEM and rides the MXU. Both share
`harness.gemm`'s output-stationary skeleton and fused requant epilogue.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import pack

from .harness import MacBody, Tile, gemm

WORD = 32


def _popcount_step(xs, ws, accs, *, bkq):
    xm, xsg = xs                            # (bm, bkq) mask/sign planes
    wm, wsg = ws[0].T, ws[1].T              # (bkq, bn)
    act, dis = accs
    # one packed word per step, unrolled: static lane/sublane slices are what
    # Mosaic lowers (a dynamic lane slice is not)
    for i in range(bkq):
        w = slice(i, i + 1)                 # word i: x column, w row
        active = jnp.bitwise_and(xm[:, w], wm[w, :])          # (bm, bn)
        disagree = jnp.bitwise_and(
            active, jnp.bitwise_xor(xsg[:, w], wsg[w, :]))
        act = act + jax.lax.population_count(active).astype(jnp.int32)
        dis = dis + jax.lax.population_count(disagree).astype(jnp.int32)
    return act, dis


def _popcount_finish(accs, k_total):
    return accs[0] - 2 * accs[1]            # dot = active - 2*disagree


TERNARY_POPCOUNT = MacBody("tgemm_popcount", n_x=2, n_w=2, n_acc=2,
                           k_per_q=WORD, step=_popcount_step,
                           finish=_popcount_finish)


def _mxu_step(xs, ws, accs, *, bkq):
    k = bkq * WORD
    xf = pack.unpack_ternary_i8(xs[0], xs[1], k).astype(jnp.float32)  # (bm, k)
    wf = pack.unpack_ternary_i8(ws[0], ws[1], k).astype(jnp.float32)  # (bn, k)
    dot = jax.lax.dot_general(xf, wf, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return (accs[0] + dot.astype(jnp.int32),)


TERNARY_MXU = MacBody("tgemm_mxu", n_x=2, n_w=2, n_acc=1, k_per_q=WORD,
                      step=_mxu_step, finish=lambda accs, k: accs[0],
                      unpacks_f32=True)


def _wt_i8a_step(xs, ws, accs, *, bkq):
    """Mixed w-ternary × a-int8: trit weight planes unpack to {-1,0,+1} int8
    in VMEM and ride the int8 MXU against the activation codes. The two
    operand sides have different storage densities — x is (bm, bkq*32) int8
    codes, w is two (bn, bkq) word planes — which is exactly what the
    harness's per-side xk_per_q/wk_per_q blocking exists for."""
    k = bkq * WORD
    w = pack.unpack_ternary_i8(ws[0], ws[1], k)             # (bn, k) trits
    dot = jax.lax.dot_general(xs[0], w, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (accs[0] + dot,)


TERNARY_W_I8A = MacBody("tgemm_wt_i8a", n_x=1, n_w=2, n_acc=1, k_per_q=WORD,
                        xk_per_q=1, wk_per_q=WORD, step=_wt_i8a_step,
                        finish=lambda accs, k: accs[0], unpacks_i8=True,
                        default_bkq=8)


def tgemm(x_mask, x_sign, w_mask, w_sign, w_scale, a_scale, *, k: int,
          bm: int = 128, bn: int = 128, bkw: int = 16,
          impl: str = "popcount", interpret: bool | None = None) -> jnp.ndarray:
    """Packed ternary GEMM: planes (M, K/32)u32 × (N, K/32)u32 → (M, N) bf16."""
    body = TERNARY_POPCOUNT if impl == "popcount" else TERNARY_MXU
    return gemm(body, (x_mask, x_sign), (w_mask, w_sign), w_scale, a_scale,
                k=k, tile=Tile(bm, bn, bkw), interpret=interpret)
