"""Binary XNOR+popcount MAC bodies — the vBMAC unit (§III).

Operands arrive bit-packed along K (32 MACs per uint32 word, v_C=32). Two
formulations of the same contract, both riding `harness.gemm`'s shared
output-stationary skeleton:

  BINARY_POPCOUNT — paper-faithful VPU path: XOR + population_count + add is
                    the direct analogue of the XNOR+popcount reduction tree;
                    the dot is recovered as K − 2·mismatches in finish().
  BINARY_MXU      — beyond-paper: unpack both packed tiles to ±1 *in VMEM*
                    and ride the MXU (dense-rate compute, packed HBM traffic).

The grid/BlockSpec/accumulator/requant-epilogue scaffold lives in
`repro.kernels.harness`; registration into the serve stack lives in
`repro.kernels.dispatch`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import pack

from .harness import MacBody, Tile, gemm

WORD = 32


def _popcount_step(xs, ws, accs, *, bkq):
    x, wt = xs[0], ws[0].T                  # (bm, bkq), (bkq, bn) uint32
    acc = accs[0]
    # one packed word per step, unrolled: static lane/sublane slices are what
    # Mosaic lowers (a dynamic lane slice is not)
    for i in range(bkq):
        mism = jax.lax.population_count(
            jnp.bitwise_xor(x[:, i:i + 1], wt[i:i + 1, :]))   # (bm, bn)
        acc = acc + mism.astype(jnp.int32)
    return (acc,)


def _popcount_finish(accs, k_total):
    return jnp.int32(k_total) - 2 * accs[0]        # dot = K - 2*mismatches


BINARY_POPCOUNT = MacBody("bgemm_popcount", n_x=1, n_w=1, n_acc=1,
                          k_per_q=WORD, step=_popcount_step,
                          finish=_popcount_finish)


def _mxu_step(xs, ws, accs, *, bkq):
    k = bkq * WORD
    xf = pack.unpack_pm1_i8(xs[0], k).astype(jnp.float32)   # (bm, 32*bkq)
    wf = pack.unpack_pm1_i8(ws[0], k).astype(jnp.float32)   # (bn, 32*bkq)
    dot = jax.lax.dot_general(xf, wf, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return (accs[0] + dot.astype(jnp.int32),)


BINARY_MXU = MacBody("bgemm_mxu", n_x=1, n_w=1, n_acc=1, k_per_q=WORD,
                     step=_mxu_step, finish=lambda accs, k: accs[0],
                     unpacks_f32=True)


def bgemm(x_packed: jnp.ndarray, w_packed: jnp.ndarray,
          w_scale: jnp.ndarray, a_scale: jnp.ndarray, *, k: int,
          bm: int = 128, bn: int = 128, bkw: int = 16,
          impl: str = "popcount", interpret: bool | None = None) -> jnp.ndarray:
    """Packed binary GEMM: (M, K/32)u32 × (N, K/32)u32 → (M, N) bf16."""
    body = BINARY_POPCOUNT if impl == "popcount" else BINARY_MXU
    return gemm(body, (x_packed,), (w_packed,), w_scale, a_scale,
                k=k, tile=Tile(bm, bn, bkw), interpret=interpret)
