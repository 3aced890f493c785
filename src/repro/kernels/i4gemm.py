"""int4 (s4 nibble-packed) MAC body — the W4A8 vMAC path.

Beyond-paper operating point between BrainTTA's ternary and int8 modes:
weights are s4 codes packed 8 per 32-bit word (v_C=8, `core.pack.pack_int4`),
activations are int8 codes. The step unpacks the nibble words to int8 *in
VMEM* (`pack.unpack_int4_i8` — the same decoder the jnp formulation uses, so
jnp-vs-pallas equivalence is an algebra check) and rides the int8 MXU; HBM
traffic stays nibble-packed. The requant epilogue composes the per-channel
int4 weight scale with the activation scale exactly like every other cell —
it lives once in `harness.gemm`.

Registration into the serve stack lives in `repro.kernels.dispatch`
(operating points w-int4 × a-int8 and weight-only int4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import pack

from .harness import MacBody, Tile, gemm


def _w4a8_step(xs, ws, accs, *, bkq):
    k = bkq * pack.NIBBLES
    w = pack.unpack_int4_i8(ws[0], k)                       # (bn, k) s4 codes
    dot = jax.lax.dot_general(xs[0], w, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (accs[0] + dot,)


INT4_W_I8A = MacBody("i4gemm_w4a8", n_x=1, n_w=1, n_acc=1,
                     k_per_q=pack.NIBBLES, xk_per_q=1, wk_per_q=pack.NIBBLES,
                     step=_w4a8_step, finish=lambda accs, k: accs[0],
                     unpacks_i8=True, default_bkq=64)


def i4gemm(x_q: jnp.ndarray, w_q4: jnp.ndarray, w_scale: jnp.ndarray,
           a_scale: jnp.ndarray, bias: jnp.ndarray | None = None, *,
           k: int, bm: int = 128, bn: int = 128, bkw: int = 64,
           interpret: bool | None = None) -> jnp.ndarray:
    """(M, K)i8 × (N, K/8)u32 nibble words → (M, N) bf16, fused requant."""
    return gemm(INT4_W_I8A, (x_q,), (w_q4,), w_scale, a_scale, bias,
                k=k, tile=Tile(bm, bn, bkw), interpret=interpret)
