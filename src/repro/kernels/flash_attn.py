"""Flash attention (online-softmax) Pallas TPU kernel — the 32k-prefill
compute hot-spot.

Not a BrainTTA contribution per se, but the prefill cells of every assigned
architecture are attention-bound at 32k context, and the paper's principle
applies verbatim: the wide accumulator (running max m, denominator l, output
acc) lives in VMEM scratch across the KV sweep and only the normalized bf16
tile is written back — "requantize as early as possible" for softmax.

Layout: q (BH, Tq, dh), k/v (BHk, Tk, dh) — GQA is expressed in the index
map (query head bh reads kv head bh // group). Grid (BH, nq, nk), nk
innermost (output-stationary in the q tile). Causal masking by absolute
block positions; fully-masked kv blocks still iterate (masked) — the
triangular-schedule skip is a known further optimization (EXPERIMENTS.md).

Validated in interpret mode against ref.flash_attention_ref over a
shape/GQA/causal sweep (tests/test_flash_attn.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, bq, bk):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (bq, dh)
    k = k_ref[0]                                   # (bk, dh)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc_new = acc_prev * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, bq: int = 256, bk: int = 256,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q: (BH, Tq, dh); k, v: (BHk, Tk, dh); BH % BHk == 0 (GQA groups).

    Returns (BH, Tq, dh) in q's dtype. Block sizes clamp to the problem and
    must divide it (ops-level callers pad).
    """
    bh, tq, dh = q.shape
    bhk, tk, _ = k.shape
    assert bh % bhk == 0
    g = bh // bhk
    bq = min(bq, tq)
    bk = min(bk, tk)
    assert tq % bq == 0 and tk % bk == 0, (tq, tk, bq, bk)
    grid = (bh, tq // bq, tk // bk)
    scale = 1.0 / dh ** 0.5
    if interpret is None:
        from .dispatch import pallas_interpret
        interpret = pallas_interpret()

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda h, i, j: (h // g, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda h, i, j: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, dh), jnp.float32)],
        interpret=interpret,
    )(q, k, v)
