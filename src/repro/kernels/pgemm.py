"""Plane-composed MAC bodies — int4/int8 weights as shifted binary planes.

BrainTTA's flexible datapath spans binary..int8 through ONE MAC array; the
weight-combination line (arXiv 2502.00687, and the Molendijk/Corporaal
mixed-precision survey) closes the loop in the other direction: a b-bit
weight is an exact shifted sum of b binary planes, so the binary datapath
serves every precision by looping planes. `core.pack.pack_planes` stores
int4/int8 codes as a stacked (b, N, K/32) uint32 tensor (MSB-first two's
complement, plane 0 = sign plane with coefficient -2^(b-1)); the step below
unpacks one plane at a time to {0,1} int8 *in VMEM*, rides the int8 MXU, and
folds the plane coefficient into the int32 accumulator:

    acc += coeff_i * (x . bits_i)        coeff_i from pack.plane_coeffs(b)

All arithmetic is integer, so the composed dot is bit-identical to the
direct int4/int8 cells (and to the dequantize-then-fp32 oracle) after the
shared requant epilogue. HBM traffic stays bit-plane packed.

The live plane depth is the operand's leading axis (static per trace): a
truncated stack `w_planes[:P]` — the self-speculative *draft* configuration
— runs the same body over fewer planes with UNCHANGED coefficients, i.e.
floor-truncated weights, at P/b of the MAC work.

Registration into the serve stack lives in `repro.kernels.dispatch`
(operating points int4 x int8 and int8 x int8, impl="planes").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import pack

from .harness import MacBody


def _planes_step(xs, ws, accs, *, bkq, bits):
    k = bkq * pack.WORD
    wp = ws[0]                      # (P, bn, bkq) uint32 planes, still a ref
    x = xs[0]                                   # (bm, k) int8 act codes

    def plane(i, acc):
        # one plane per iteration, loaded from the ref: an unrolled plane
        # loop multiplies the unpacked (bn, k) code the compiler must emit
        bits_i = pack.unpack_bits_i32(wp[i], k).astype(jnp.int8)  # {0,1}
        dot = jax.lax.dot_general(x, bits_i, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        # pack.plane_coeffs(bits)[i]: -2^(b-1) for the sign plane, then
        # 2^(b-1-i)
        coeff = jnp.where(i == 0, -(1 << (bits - 1)),
                          jnp.left_shift(1, bits - 1 - i))
        return acc + coeff * dot

    return (jax.lax.fori_loop(0, wp.shape[0], plane, accs[0]),)


def _mk(bits: int, name: str) -> MacBody:
    return MacBody(name, n_x=1, n_w=1, n_acc=1,
                   k_per_q=pack.WORD, xk_per_q=1, wk_per_q=pack.WORD,
                   step=functools.partial(_planes_step, bits=bits),
                   finish=lambda accs, k: accs[0],
                   unpacks_i8=True, default_bkq=8, w_stack=bits,
                   w_ref=True)


PLANES_W4_I8A = _mk(4, "pgemm_w4a8_planes")
PLANES_W8_I8A = _mk(8, "pgemm_w8a8_planes")
