"""Bit-plane packing — BrainTTA's v_C operands-per-word storage (§IV-B).

The SoC packs 32 binary / 16 ternary / 4 int8 operands into each 32-bit word
so a 1024-bit vector holds one vMAC input. On TPU the analogous layout packs
the *contraction* (K) axis of a GEMM into int32 words:

  binary : K/32 words, bit k of word j  = code of operand j*32+k
  ternary: two planes (mask, sign), each K/32 words of 1-bit fields
           (a trit is 2 bits *across planes*, matching v_C=16 per 32-bit
            word-pair of storage)
  int8   : native int8 arrays (4 per 32-bit word is the hardware's native
           byte layout already; XLA handles it)

Packing always happens along the LAST axis; callers move K last first.
K must be a multiple of 32 (pad upstream — model dims here are all
multiples of 128, cf. paper's "multiples of v_C for full utilization",
Table I flexibility rows).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WORD = 32  # bits per packed word
NIBBLES = 8  # int4 codes per 32-bit word (v_C=8 for the s4 format)

#: total bit-planes of each plane-decomposable weight precision (two's
#: complement: plane 0 is the sign plane, coefficient -2^(b-1))
PLANE_BITS = {"int4": 4, "int8": 8}

#: K elements per unit of each packed leaf's storage axis — THE pack-factor
#: table every layer consults (`kernels.dispatch.tp_plan` for shard_map
#: compute, `launch.sharding` for device layout). A leaf absent here is
#: unpacked (one element per storage unit).
K_QUANTUM = {"w_packed": WORD, "w_mask": WORD, "w_sign": WORD,
             "w_q4": NIBBLES, "w_planes": WORD}


def shardable_words(units: int, n_shards: int) -> bool:
    """True iff a storage axis of `units` whole quanta (packed 32-operand
    words for the bit-plane formats, int8 codes for the 8-bit format) splits
    into `n_shards` equal whole-quantum shards.

    This is THE divisibility rule for tensor-parallel K-sharding of packed
    operands: a shard boundary may never fall inside a packed word (the
    XNOR/gated-XNOR word algebra contracts whole words), so sharding the
    packed axis of `w_packed`/`w_mask`/`w_sign` requires K to divide
    pack_factor(32) x n_shards. Both `launch.sharding` (device layout) and
    `kernels.dispatch` (shard_map compute) consult this one predicate so the
    two can never disagree about whether a leaf is K-shardable.
    """
    return n_shards > 0 and units % n_shards == 0


def _check_k(k: int) -> None:
    if k % WORD:
        raise ValueError(f"packing axis length {k} not a multiple of {WORD}")


def pack_bits(codes: jnp.ndarray) -> jnp.ndarray:
    """Pack 0/1 codes (uint8, last axis = K) into int32 words (last axis K/32).

    Bit k of word j holds code[..., j*32+k] (little-endian within the word).
    """
    _check_k(codes.shape[-1])
    c = codes.reshape(*codes.shape[:-1], codes.shape[-1] // WORD, WORD)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    words = jnp.sum(c.astype(jnp.uint32) << shifts, axis=-1)
    return words.astype(jnp.uint32)


def unpack_bits_i32(words: jnp.ndarray, k: int) -> jnp.ndarray:
    """0/1 codes as int32 with last axis k. The int8 decoders below do their
    arithmetic here and narrow once at the end: the TPU vector unit has no
    int8 multiply, so int8 arithmetic does not lower inside a kernel."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = ((words[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.int32)
    return bits.reshape(*words.shape[:-1], words.shape[-1] * WORD)[..., :k]


def unpack_bits(words: jnp.ndarray, k: int) -> jnp.ndarray:
    """Inverse of pack_bits -> uint8 codes with last axis k."""
    return unpack_bits_i32(words, k).astype(jnp.uint8)


# -- binary ------------------------------------------------------------------

def pack_binary(values: jnp.ndarray) -> jnp.ndarray:
    """Pack {-1,+1} float values: bit=1 encodes +1."""
    return pack_bits((values >= 0).astype(jnp.uint8))


def unpack_binary(words: jnp.ndarray, k: int) -> jnp.ndarray:
    """Unpack to {-1,+1} float32."""
    bits = unpack_bits(words, k)
    return jnp.where(bits == 1, 1.0, -1.0).astype(jnp.float32)


def unpack_pm1_i8(words: jnp.ndarray, k: int) -> jnp.ndarray:
    """Unpack bit-plane words to ±1 int8 along a new last axis of length k.

    The canonical plane->operand decoder for the MXU formulations (jnp and
    Pallas tile bodies both call this — one unpack implementation total).
    """
    return (unpack_bits_i32(words, k) * 2 - 1).astype(jnp.int8)


# -- ternary -----------------------------------------------------------------

def pack_ternary(values: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pack {-1,0,+1} floats into (mask_words, sign_words) planes."""
    mask = (values != 0).astype(jnp.uint8)
    sign = (values < 0).astype(jnp.uint8)
    return pack_bits(mask), pack_bits(sign)


def unpack_ternary(mask_words: jnp.ndarray, sign_words: jnp.ndarray, k: int) -> jnp.ndarray:
    """Unpack planes to {-1,0,+1} float32."""
    mask = unpack_bits(mask_words, k).astype(jnp.float32)
    sign = unpack_bits(sign_words, k)
    return mask * jnp.where(sign == 1, -1.0, 1.0)


def unpack_ternary_i8(mask_words: jnp.ndarray, sign_words: jnp.ndarray,
                      k: int) -> jnp.ndarray:
    """Unpack trit planes to {-1,0,+1} int8 (canonical MXU-path decoder)."""
    mask = unpack_bits_i32(mask_words, k)
    sign = unpack_bits_i32(sign_words, k)
    return (mask * (1 - 2 * sign)).astype(jnp.int8)


# -- int4 (s4 nibble codes, 8 per word) --------------------------------------

def pack_int4(codes: jnp.ndarray) -> jnp.ndarray:
    """Pack s4 codes in [-8,7] (int dtype, last axis = K) into uint32 words.

    Nibble j of word i holds code[..., i*8+j] in two's complement
    (little-endian within the word), so K/8 words per row — v_C=8.
    """
    k = codes.shape[-1]
    if k % NIBBLES:
        raise ValueError(f"int4 packing axis length {k} not a multiple of {NIBBLES}")
    c = codes.astype(jnp.int32) & 0xF
    c = c.reshape(*codes.shape[:-1], k // NIBBLES, NIBBLES)
    shifts = jnp.arange(NIBBLES, dtype=jnp.uint32) * 4
    return jnp.sum(c.astype(jnp.uint32) << shifts, axis=-1).astype(jnp.uint32)


def unpack_int4_i8(words: jnp.ndarray, k: int) -> jnp.ndarray:
    """Unpack s4 nibble words to int8 codes along a last axis of length k.

    The canonical word->operand decoder for the int4 MXU formulations — the
    jnp accumulator and the Pallas MacBody both call this, so jnp-vs-pallas
    equivalence stays an algebra check. Sign extension is arithmetic
    (nibble >= 8 => nibble - 16), keeping the whole path integer."""
    shifts = jnp.arange(NIBBLES, dtype=jnp.uint32) * 4
    nib = ((words[..., None] >> shifts) & jnp.uint32(0xF)).astype(jnp.int32)
    nib = nib.reshape(*words.shape[:-1], words.shape[-1] * NIBBLES)[..., :k]
    return jnp.where(nib >= 8, nib - 16, nib).astype(jnp.int8)


# -- bit-plane stacks (int4/int8 as shifted sums of binary planes) -----------
#
# Exact two's-complement decomposition of a b-bit code c:
#
#     c = -2^(b-1) * bit_{b-1} + sum_{j<b-1} 2^j * bit_j
#
# stored MSB-first along a NEW plane axis inserted before the last two axes,
# so a (N, K) code matrix becomes a (b, N, K/32) uint32 stack and an expert
# stack (E, N, K) becomes (E, b, N, K/32). MSB-first ordering makes plane
# truncation a leading slice `w_planes[:P]` with UNCHANGED per-plane
# coefficients — the storage trick self-speculative decoding exploits (a
# truncated-plane pass over the same weights is the draft model). The plane
# axis never touches the K storage axis, so K_QUANTUM["w_planes"] stays the
# 32-operand word quantum and the tensor-parallel shard rules apply verbatim.


def plane_coeffs(bits: int) -> tuple[int, ...]:
    """MSB-first per-plane coefficients of the b-bit two's-complement
    decomposition: (-2^(b-1), 2^(b-2), ..., 2, 1). Python ints — static in
    every jit trace, so truncated stacks keep their original coefficients."""
    if not 2 <= bits <= 8:
        raise ValueError(f"plane decomposition supports 2..8 bits, got {bits}")
    return (-(1 << (bits - 1)),) + tuple(
        1 << (bits - 1 - i) for i in range(1, bits))


def pack_planes(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Decompose b-bit two's-complement codes (int dtype, last axis = K) into
    a stacked bit-plane tensor: uint32 (..., bits, N, K/32), MSB-first.

    Bit-exact inverse is `unpack_planes_i8(planes, k, bits)`; a leading
    slice `planes[..., :P, :, :]` is the truncated-plane approximation
    (floor(c / 2^(b-P)) * 2^(b-P), rounding toward -inf)."""
    coeffs = plane_coeffs(bits)          # validates bits
    del coeffs
    _check_k(codes.shape[-1])
    if codes.ndim < 2:
        raise ValueError("pack_planes needs at least a (N, K) matrix")
    field = codes.astype(jnp.int32) & ((1 << bits) - 1)   # b-bit 2c field
    planes = [pack_bits(((field >> (bits - 1 - i)) & 1).astype(jnp.uint8))
              for i in range(bits)]
    return jnp.stack(planes, axis=-3)


def unpack_planes_i8(planes: jnp.ndarray, k: int, bits: int) -> jnp.ndarray:
    """Compose a (possibly truncated) plane stack back to int8 codes.

    planes: uint32 (..., P, N, K/32) with P <= bits leading (MSB-first)
    planes of the ORIGINAL b-bit decomposition; k: unpacked K. P == bits
    reproduces the stored codes exactly (round-trip contract); P < bits
    gives the truncation floor(c / 2^(b-P)) * 2^(b-P). The canonical
    plane->operand decoder — the jnp accumulator and the Pallas MacBody
    both derive from the same coefficients, so jnp-vs-pallas equivalence
    stays an algebra check."""
    p_live = planes.shape[-3]
    coeffs = jnp.asarray(plane_coeffs(bits)[:p_live], jnp.int32)
    bitsmat = unpack_bits(planes, k).astype(jnp.int32)    # (..., P, N, k)
    vals = jnp.sum(bitsmat * coeffs[..., :, None, None], axis=-3)
    return vals.astype(jnp.int8)


# -- packed dot products (the XNOR/gated-XNOR algebra, §II-A) ----------------

def binary_dot_words(x_words: jnp.ndarray, w_words: jnp.ndarray, k: int) -> jnp.ndarray:
    """Binary dot product over packed words: sum_i x_i * w_i, x,w in {-1,+1}.

    XNOR-popcount identity: matches = K - popcount(x ^ w);
    dot = matches - mismatches = K - 2*popcount(x ^ w).
    Contracts the last axis of both operands (word axis).
    """
    mismatch = jnp.sum(
        jax.lax.population_count(jnp.bitwise_xor(x_words, w_words)).astype(jnp.int32),
        axis=-1,
    )
    return jnp.int32(k) - 2 * mismatch


def ternary_dot_words(
    xm: jnp.ndarray, xs: jnp.ndarray, wm: jnp.ndarray, ws: jnp.ndarray
) -> jnp.ndarray:
    """Gated-XNOR dot product over packed trit planes (§II-A).

    active = xm & wm (both non-zero); within active lanes the product is
    +1 where signs agree, -1 where they differ:
        dot = popcount(active & ~(xs^ws)) - popcount(active & (xs^ws))
            = popcount(active) - 2*popcount(active & (xs^ws))
    """
    active = jnp.bitwise_and(xm, wm)
    disagree = jnp.bitwise_and(active, jnp.bitwise_xor(xs, ws))
    pc = lambda v: jnp.sum(jax.lax.population_count(v).astype(jnp.int32), axis=-1)
    return pc(active) - 2 * pc(disagree)
