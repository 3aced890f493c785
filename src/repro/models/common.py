"""Shared model components: norms, RoPE, activations, embeddings, conv1d,
and the quantized-linear helpers every block builds on.

All modules are functional: `*_init(rng, ...) -> params`, `*_apply(params, ...)`.
Compute dtype is bf16, norms/softmax/router in f32 (the "wide residual
stream" — BrainTTA keeps accumulators wide and requantizes at operator
egress, §IV-B).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qlinear
from repro.core.precision import LayerQuant, PrecisionPolicy
from repro.core.qlinear import QLinearSpec
from repro.kernels.dispatch import round_to


@dataclasses.dataclass(frozen=True)
class ModelCtx:
    """Execution context threaded through every block."""
    mode: str = "train"          # "train" (QAT) | "serve" (packed)
    backend: str = "jnp"         # "jnp" | "pallas"
    impl: str = "popcount"       # binary/ternary GEMM formulation
    dtype: jnp.dtype = jnp.bfloat16
    act_dp: tuple | None = None  # dp mesh axes to pin activations' batch dim to
    attn_cp: str | None = None   # mesh axis for context-parallel attention
                                 # (q sequence sharded; kv replicated per dp
                                 # group — head-count agnostic, unlike head-TP)
    fsdp_wire: str = "dense"     # "packed": FSDP gathers move the 1/2/8-bit
                                 # planes instead of bf16 weights (§Perf B)
    tp: object | None = None     # kernels.dispatch.TPSpec: serve-mode tensor
                                 # parallelism — qgemm runs under shard_map in
                                 # each layer's spec.parallel role (set by the
                                 # --mesh serving driver; None everywhere else)
    tune: object | None = None   # kernels.dispatch.TuneTable override: per-
                                 # cell Tile choices (None = the shipped CPU
                                 # default table inside dispatch)
    paged_attn: str = "auto"     # paged decode-attention path: "auto" (fused
                                 # Pallas kernel iff backend == "pallas"),
                                 # "fused" (force the kernel), "gather"
                                 # (force the jnp oracle path)
    draft_planes: int | None = None  # self-speculative DRAFT context: layers
                                 # resolving to a plane-composed cell contract
                                 # only the leading N MSB planes (clamped to
                                 # the cell's stack depth); other layers run
                                 # full precision. None everywhere but the
                                 # serve driver's draft pass.
    ep: object | None = None     # kernels.dispatch.EPSpec: serve-mode expert
                                 # parallelism — expert-stacked qgemms run the
                                 # grouped dispatch (each shard computes only
                                 # its local experts) instead of the
                                 # replicated dense vmap. Set by the --mesh
                                 # serving driver for MoE archs; None
                                 # everywhere else.
    moe_stats: bool = False      # surface per-step MoE routing stats: the
                                 # top-level serve entry points return a third
                                 # {"expert_tokens": (E,) i32, "dropped": i32,
                                 # "rows": i32} value summed over MoE blocks
                                 # (Server.stats feeds on it). Off => 2-tuple
                                 # returns, so non-MoE callers and lowering
                                 # probes keep their shapes.


TRAIN = ModelCtx(mode="train")


def shard_act(x, ctx: "ModelCtx"):
    """Pin a (B, ...) activation's batch dim to the dp axes. Without this,
    GSPMD can resolve the FSDP/TP weight shardings by replicating the batch —
    catastrophic activation all-gathers (seen: 32 GiB logit gathers)."""
    if ctx.act_dp is None:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(tuple(ctx.act_dp), *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def shard_spec(x, ctx: "ModelCtx", *dims):
    """with_sharding_constraint with explicit trailing dims (batch first)."""
    if ctx.act_dp is None:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(tuple(ctx.act_dp), *dims))


# NOTE (serve TP): there is deliberately no "pin this activation axis to the
# model mesh axis" helper for the serve path. Head sharding flows from the
# column-parallel qkv shard_map out_specs; an explicit
# with_sharding_constraint on the head axis made the CPU SPMD partitioner
# miscompile the blocked-attention scan (value-level divergence caught by
# tests/test_serving_tp.py's token-exact oracle). Let the shard_map
# boundaries dictate placement instead.


# -- linear helper ------------------------------------------------------------

def lspec(pol: PrecisionPolicy, layer_class: str, in_dim: int, out_dim: int, *,
          first: bool = False, last: bool = False, bias: bool = False,
          experts: int = 0, name: str = "",
          parallel: str = "none") -> QLinearSpec:
    lq = pol.lookup(layer_class, is_first=first, is_last=last)
    return QLinearSpec(in_dim, out_dim, lq, use_bias=bias, experts=experts,
                       name=name or layer_class, parallel=parallel)


def linear_init(rng, spec: QLinearSpec, dtype=jnp.float32):
    return qlinear.init(rng, spec, dtype)


def linear_apply(p, x, spec: QLinearSpec, ctx: ModelCtx):
    if ctx.mode == "serve":
        y = qlinear.apply(p, x, spec, mode="serve",
                          op=operating_point(spec, ctx), tp=ctx.tp,
                          ep=ctx.ep)
    else:
        y = qlinear.apply(p, x, spec, mode=ctx.mode, wire=ctx.fsdp_wire)
    return y.astype(ctx.dtype)


def operating_point(spec: QLinearSpec, ctx: ModelCtx):
    """Resolve THIS layer's `dispatch.OperatingPoint`: precisions from the
    layer's policy assignment (spec.lq), formulation/backend from the
    execution context, tile from the context's TuneTable when one is loaded
    (else qgemm falls back to the shipped default table). This per-layer
    resolution is what lets one policy serve heterogeneous operating points
    — e.g. s4 ffn_up next to ternary attn_out — with no global flag pair."""
    from repro.core import pack
    from repro.kernels import dispatch
    from repro.kernels.dispatch import OperatingPoint
    op = OperatingPoint.for_spec(spec, impl=ctx.impl, backend=ctx.backend)
    try:
        cell = dispatch.lookup(op)
    except KeyError:
        # impl fallback: a formulation only SOME pairs implement (e.g.
        # impl="planes" exists for int4/int8 x int8 only) resolves per layer
        # — pairs without it run their default cell instead of erroring, so
        # one --impl planes flag serves a heterogeneous policy end to end.
        op = dataclasses.replace(op, impl="popcount")
        cell = dispatch.lookup(op)
    if ctx.draft_planes is not None and "w_planes" in cell.weight_names:
        op = dataclasses.replace(
            op, planes=min(ctx.draft_planes, pack.PLANE_BITS[op.wprec]))
    if ctx.tune is not None:
        op = dataclasses.replace(op, tile=ctx.tune.tile_for(op))
    return op


def pack_linear(p, spec: QLinearSpec):
    return qlinear.pack_params(p, spec)


# -- norms --------------------------------------------------------------------

def norm_init(d: int, kind: str = "rmsnorm", dtype=jnp.float32):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def residual(x, y):
    """The residual add x + y, in f32 and rounded to x's dtype explicitly
    (`dispatch.round_to`). A bf16 add would leave the rounding to XLA,
    which with excess precision keeps the unrounded sum wherever the add
    fuses into its consumer: the values would then depend on fusion, which
    differs between the Pallas and XLA backends."""
    return round_to(x.astype(jnp.float32) + y.astype(jnp.float32), x.dtype)


def gated(act, g, u, dtype):
    """act(g) * u of a gated FFN, in f32 and rounded to `dtype` explicitly,
    for the reason `residual` gives."""
    return round_to(act(g.astype(jnp.float32)) * u.astype(jnp.float32), dtype)


def norm_apply(p, x, kind: str = "rmsnorm", eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (xf * inv * p["scale"]).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]).astype(x.dtype)


# -- activations ----------------------------------------------------------------

def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "squared_relu":                      # nemotron-4
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# -- rotary embeddings ----------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Apply RoPE. x: (B, T, H, dh), positions: (B, T) or (T,)."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[..., None] * freqs        # (..., T, dh/2)
    if ang.ndim == 2:                                             # (T, dh/2)
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(t: int, d: int) -> jnp.ndarray:
    """Whisper-style sinusoidal embedding table (T, D)."""
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    inv = 1.0 / (10_000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# -- embedding ------------------------------------------------------------------

def embed_init(rng, vocab: int, d: int, dtype=jnp.float32):
    return {"w": jax.random.normal(rng, (vocab, d), dtype) * 0.02}


def embed_apply(p, tokens: jnp.ndarray, dtype=jnp.bfloat16):
    return jnp.take(p["w"], tokens, axis=0).astype(dtype)


# -- causal temporal conv (xLSTM / RG-LRU frontends) -----------------------------

def conv1d_init(rng, d: int, width: int = 4, dtype=jnp.float32):
    return {"w": jax.random.normal(rng, (width, d), dtype) * (1.0 / width ** 0.5),
            "b": jnp.zeros((d,), dtype)}


def conv1d_apply(p, x: jnp.ndarray, state: jnp.ndarray | None = None):
    """Depthwise causal conv. x: (B, T, D). state: (B, width-1, D) for decode.

    Returns (y, new_state). Training: state=None -> zero left-pad.
    """
    w = p["w"].astype(x.dtype)
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], width - 1, x.shape[-1]), x.dtype)
    xx = jnp.concatenate([state, x], axis=1)                      # (B, T+w-1, D)
    y = sum(xx[:, i:i + x.shape[1], :] * w[i] for i in range(width))
    new_state = xx[:, -(width - 1):, :] if width > 1 else state
    return (y + p["b"].astype(x.dtype), new_state)


# -- serve-side token sampling ----------------------------------------------------

def sample_token(logits_row, temperature: float, seed: int, index: int) -> int:
    """Host-side next-token draw for the serving loop (and its test oracles).

    temperature <= 0 is greedy argmax. Otherwise a categorical draw from
    softmax(logits / T) using a STATELESS numpy rng keyed by (seed, index) —
    no mutable stream, so token `index` of a request reproduces bit-exactly
    no matter how the request was batched, preempted/resumed, or
    prefix-shared in between. That determinism is what lets the scheduler
    tests demand token-exact equality against a sequential oracle, and what
    makes copy-on-write observable at all: two requests sharing a prompt
    prefix diverge only through (seed, temperature).

    Runs on host float64 from the f32 logits — identical logits therefore
    always give identical tokens (argmax ties break to the lowest index on
    both np and jnp).
    """
    row = np.asarray(logits_row, np.float64).reshape(-1)
    if temperature <= 0.0:
        return int(np.argmax(row))
    z = row / float(temperature)
    z -= z.max()
    p = np.exp(z)
    p /= p.sum()
    rng = np.random.default_rng((int(seed) & 0x7FFFFFFF, int(index)))
    return int(rng.choice(row.shape[0], p=p))


# -- loss -------------------------------------------------------------------------

def cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray,
                  mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Token-level CE. logits: (B, T, V) any float dtype, targets: (B, T) int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
