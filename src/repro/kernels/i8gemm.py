"""int8 MXU MAC body — the 8-bit vMAC path.

BrainTTA's 8-bit mode (v_C=4 operands/word) maps directly onto the TPU MXU's
native int8×int8→int32 path — this is where the ASIC→TPU translation is an
upgrade, not an emulation. The BrainTTA-specific part (the requantization
epilogue fused immediately behind the MAC, §IV-B) lives once in
`harness.gemm`; this module is just the dot body. Weight codes use the
K-major (K, N) layout XLA's int8 dot prefers, hence w_kmajor=True.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .harness import MacBody, Tile, gemm


def _i8_step(xs, ws, accs, *, bkq):
    dot = jax.lax.dot_general(xs[0], ws[0], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (accs[0] + dot,)


I8_DOT = MacBody("i8gemm", n_x=1, n_w=1, n_acc=1, k_per_q=1,
                 step=_i8_step, finish=lambda accs, k: accs[0],
                 w_kmajor=True, default_bkq=512)


def i8gemm(x_q: jnp.ndarray, w_q: jnp.ndarray, w_scale: jnp.ndarray,
           a_scale: jnp.ndarray, bias: jnp.ndarray | None = None, *,
           bm: int = 128, bn: int = 128, bk: int = 512,
           interpret: bool | None = None) -> jnp.ndarray:
    """(M, K)i8 × (K, N)i8 → (M, N) bf16 with fused requant epilogue."""
    return gemm(I8_DOT, (x_q,), (w_q,), w_scale, a_scale, bias,
                k=x_q.shape[1], tile=Tile(bm, bn, bk), interpret=interpret)
