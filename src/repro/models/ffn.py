"""Feed-forward blocks: gated (SwiGLU/GeGLU) and plain (squared-ReLU, nemotron).

Both up and down projections are QuantizedLinears (BrainTTA layer types 1/5);
the activation between them runs wide, requantization happens at the next
linear's ingress (§IV-B "requantize as early as possible" maps to: the narrow
format is the *storage/transport* format, the nonlinearity runs on the wide
accumulator before requant).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.precision import PrecisionPolicy

from . import common
from .common import ModelCtx


@dataclasses.dataclass(frozen=True)
class FFNSpecs:
    up: Any
    down: Any
    gated: bool
    act: str


def ffn_specs(cfg: ArchConfig, pol: PrecisionPolicy, *, first=False, last=False,
              d_ff: int = 0) -> FFNSpecs:
    # serve TP (Megatron pairing): up is column-parallel (hidden dim sharded,
    # no collective), down is row-parallel (packed-K sharded, one
    # pre-requant int32 psum per block)
    f = d_ff or cfg.d_ff
    up_out = 2 * f if cfg.gated_ffn else f
    return FFNSpecs(
        up=common.lspec(pol, "ffn_up", cfg.d_model, up_out, first=first,
                        last=last, parallel="column"),
        down=common.lspec(pol, "ffn_down", f, cfg.d_model, first=first,
                          last=last, parallel="row"),
        gated=cfg.gated_ffn,
        act=cfg.act_fn,
    )


def ffn_init(rng, specs: FFNSpecs, dtype=jnp.float32):
    k1, k2 = jax.random.split(rng)
    return {"up": common.linear_init(k1, specs.up, dtype),
            "down": common.linear_init(k2, specs.down, dtype)}


def ffn_apply(p, x, specs: FFNSpecs, ctx: ModelCtx):
    h = common.linear_apply(p["up"], x, specs.up, ctx)
    act = common.activation(specs.act)
    if specs.gated:
        g, u = jnp.split(h, 2, axis=-1)
        h = common.gated(act, g, u, x.dtype)
    else:
        h = act(h.astype(jnp.float32)).astype(x.dtype)
    return common.linear_apply(p["down"], h, specs.down, ctx)
