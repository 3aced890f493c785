"""Pure-jnp oracles for the Pallas GEMM kernels.

These define the exact semantics each kernel must reproduce; the kernel tests
sweep shapes/dtypes and assert_allclose against these. All three GEMMs share
the BrainTTA contract (DESIGN.md §6):

  out[m, n] = requant( sum_k x[m, k] * w[n, k] )   with the fused epilogue
  requant(acc) = acc * w_scale[n] * a_scale[m]  (+ bias[n])        -> bf16

Operand encodings match `repro.core.pack`.

The oracles with a bias epilogue are jitted like the kernels they check:
XLA:CPU contracts `acc * scale + bias` into one fused multiply-add inside a
compiled fusion, while op-by-op eager dispatch rounds the product first, so
an eager oracle differs from any compiled kernel by one f32 ulp (enough to
flip a bf16 rounding). Compiling both sides makes the epilogue the same
arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import pack


def binary_gemm_ref(x_packed: jnp.ndarray, w_packed: jnp.ndarray, k: int,
                    w_scale: jnp.ndarray, a_scale: jnp.ndarray) -> jnp.ndarray:
    """XNOR-popcount GEMM oracle.

    x_packed: (M, K/32) uint32, w_packed: (N, K/32) uint32,
    w_scale: (N,) f32, a_scale: (M,) f32 -> (M, N) bf16.
    """
    x = pack.unpack_binary(x_packed, k)          # (M, K) in {-1,+1}
    w = pack.unpack_binary(w_packed, k)          # (N, K)
    acc = x @ w.T                                # exact in f32 (values ±K)
    return (acc * w_scale[None, :] * a_scale[:, None]).astype(jnp.bfloat16)


def ternary_gemm_ref(x_mask, x_sign, w_mask, w_sign, k: int,
                     w_scale, a_scale) -> jnp.ndarray:
    """Gated-XNOR popcount GEMM oracle (trit planes)."""
    x = pack.unpack_ternary(x_mask, x_sign, k)   # (M, K) in {-1,0,+1}
    w = pack.unpack_ternary(w_mask, w_sign, k)   # (N, K)
    acc = x @ w.T
    return (acc * w_scale[None, :] * a_scale[:, None]).astype(jnp.bfloat16)


@jax.jit
def i8_gemm_ref(x_q: jnp.ndarray, w_q: jnp.ndarray,
                w_scale: jnp.ndarray, a_scale: jnp.ndarray,
                bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """int8 GEMM oracle with fused requant epilogue.

    x_q: (M, K) int8, w_q: (K, N) int8, w_scale: (N,), a_scale: (M,) -> bf16.
    """
    acc = jax.lax.dot(x_q.astype(jnp.int32), w_q.astype(jnp.int32))
    y = acc.astype(jnp.float32) * w_scale[None, :] * a_scale[:, None]
    if bias is not None:
        y = y + bias[None, :]
    return y.astype(jnp.bfloat16)


def binary_gemm_mxu_ref(x_packed, w_packed, k: int, w_scale, a_scale) -> jnp.ndarray:
    """Oracle for the beyond-paper MXU formulation — semantics identical to
    binary_gemm_ref (the formulations must agree bit-exactly on the int acc)."""
    return binary_gemm_ref(x_packed, w_packed, k, w_scale, a_scale)


@functools.partial(jax.jit, static_argnames="k")
def wt_i8a_gemm_ref(x_q, w_mask, w_sign, k: int, w_scale, a_scale,
                    bias=None) -> jnp.ndarray:
    """Mixed w-ternary × a-int8 oracle: int8 codes against unpacked trits.

    x_q: (M, K) int8, trit planes (N, K/32) uint32 -> (M, N) bf16. The
    requant composes the ternary per-channel alpha with the int8 activation
    scale — no matched-precision assumption.
    """
    w = pack.unpack_ternary(w_mask, w_sign, k)   # (N, K) in {-1,0,+1}
    acc = x_q.astype(jnp.float32) @ w.T          # exact: small ints in f32
    y = acc * w_scale[None, :] * a_scale[:, None]
    if bias is not None:
        y = y + bias[None, :]
    return y.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames="k")
def i4_gemm_ref(x_q, w_q4, k: int, w_scale, a_scale, bias=None) -> jnp.ndarray:
    """int4-weight (s4 nibble words) × int8-activation oracle.

    x_q: (M, K) int8, w_q4: (N, K/8) uint32 -> (M, N) bf16.
    """
    w = pack.unpack_int4_i8(w_q4, k).astype(jnp.float32)   # (N, K) in [-7,7]
    acc = x_q.astype(jnp.float32) @ w.T
    y = acc * w_scale[None, :] * a_scale[:, None]
    if bias is not None:
        y = y + bias[None, :]
    return y.astype(jnp.bfloat16)
