"""Mixture-of-Experts with GShard-style capacity dispatch + expert parallelism.

Supports phi3.5-moe (16 experts, top-2) and deepseek-moe (2 shared + 64
routed, top-6, fine-grained d_ff). Expert FFN weights carry a leading expert
axis that `launch/sharding.py` places on the "model" mesh axis (EP); under a
serve mesh with `ctx.ep` set, the expert qgemms run the grouped expert
dispatch (`kernels.dispatch._ep_column`/`_ep_row`) — each shard computes
only its local experts on their capacity-dispatched token slabs, with one
psum assembling the down projection (see docs/MOE.md).

Router stays fp32 and unquantized (core.precision.ALWAYS_WIDE): it is tiny
and accuracy-critical — BrainTTA's "sensitive layers stay wide" rule. It is
also REPLICATED under EP: every shard routes identically, which is what
makes capacity drops deterministic and shard-count independent.

Dispatch uses the dense (B,S,E,C) one-hot formulation: static shapes (SPMD-
friendly), with token dropping at capacity. Sort-based ragged dispatch is the
documented beyond-paper alternative (EXPERIMENTS.md §Perf).

Determinism contract (the token-exact-vs-oracle bar): `jax.lax.top_k`
breaks gate ties toward the lowest expert index, and capacity slots are
assigned by flat (s*k) cumsum position — both pure functions of the gate
values, no RNG, no device-count dependence. EP serving therefore drops
exactly the tokens the single-device dense-vmap oracle drops, and
`tests/test_moe_serving.py` holds the outputs bit-equal.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.precision import PrecisionPolicy

from . import common, ffn
from .common import ModelCtx


@dataclasses.dataclass(frozen=True)
class MoESpecs:
    router: Any
    up: Any
    down: Any
    shared: Any            # FFNSpecs | None
    n_experts: int
    top_k: int
    capacity_factor: float
    gated: bool
    act: str


def moe_specs(cfg: ArchConfig, pol: PrecisionPolicy, *, first=False, last=False) -> MoESpecs:
    e, f, d = cfg.n_experts, cfg.d_ff, cfg.d_model
    up_out = 2 * f if cfg.gated_ffn else f
    # serve meshes: the parallel= markers feed BOTH plans. EP (preferred,
    # ctx.ep) shards the leading expert axis — column runs local experts
    # with no collective, row assembles with one disjoint psum. When ep_plan
    # declines (E % shards != 0), the same markers drive Megatron pairing
    # *within* each expert — expert axis unsharded, each expert's up/down
    # sharding N / packed-K, one row psum over the whole stack (dispatch/
    # combine einsums stay global). The router is tiny and replicated.
    return MoESpecs(
        router=common.lspec(pol, "moe_router", d, e),
        up=common.lspec(pol, "moe_expert", d, up_out, first=first, last=last,
                        experts=e, parallel="column"),
        down=common.lspec(pol, "moe_expert", f, d, first=first, last=last,
                          experts=e, parallel="row"),
        shared=(ffn.ffn_specs(cfg, pol, first=first, last=last,
                              d_ff=cfg.n_shared_experts * f)
                if cfg.n_shared_experts else None),
        n_experts=e, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
        gated=cfg.gated_ffn, act=cfg.act_fn,
    )


def moe_init(rng, specs: MoESpecs, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    p = {"router": common.linear_init(ks[0], specs.router, dtype),
         "up": common.linear_init(ks[1], specs.up, dtype),
         "down": common.linear_init(ks[2], specs.down, dtype)}
    if specs.shared is not None:
        p["shared"] = ffn.ffn_init(ks[3], specs.shared, dtype)
    return p


def _capacity(s: int, specs: MoESpecs) -> int:
    """Slots per expert and batch row: the capacity factor's share, rounded
    up to a multiple of 4 and at least 4, but never more than s. top_k picks
    distinct experts, so one row gives an expert at most s assignments: the
    cap drops nothing the uncapped value keeps, and a decode (s = 1) computes
    one row per slot."""
    c = int(s * specs.top_k / specs.n_experts * specs.capacity_factor)
    return min(s, max(4, (c + 3) // 4 * 4))


def moe_apply(p, x, specs: MoESpecs, ctx: ModelCtx):
    """x: (B, S, D) -> (B, S, D). Dense-dispatch MoE with top-k routing.

    Returns (y, aux) where aux is a dict:
      "loss"          — scalar Switch-style load-balancing term (train)
      "expert_tokens" — (E,) int32, tokens·top-k assignments that landed a
                        capacity slot on each expert this call (utilization)
      "dropped"       — int32, assignments past capacity (dropped this call)
      "rows"          — int, rows the expert GEMMs compute this call
                        (E · B·C, capacity padding included); static
    The counters are exact under EP because routing is replicated; the
    serve driver accumulates them into `Server.stats` when ctx.moe_stats.

    The phases run under named scopes (moe_router, moe_dispatch,
    moe_experts, moe_combine, moe_shared) that label their ops in the HLO
    metadata and the device trace; they change no computation.
    """
    b, s, d = x.shape
    e, k = specs.n_experts, specs.top_k
    c = _capacity(s, specs)

    with jax.named_scope("moe_router"):
        logits = common.linear_apply(p["router"], x, specs.router,
                                     ctx).astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)                  # (B,S,E)
        topv, topi = jax.lax.top_k(gates, k)                     # (B,S,K)
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    with jax.named_scope("moe_dispatch"):
        sel = jax.nn.one_hot(topi, e, dtype=jnp.float32)         # (B,S,K,E)
        flat = sel.reshape(b, s * k, e)
        pos = jnp.cumsum(flat, axis=1) - flat            # slots before me
        pos = jnp.sum(flat * pos, axis=-1).reshape(b, s, k).astype(jnp.int32)
        keep = (pos < c).astype(jnp.float32)
        pos_oh = jax.nn.one_hot(pos, c, dtype=jnp.float32)       # (B,S,K,C)

        # dispatch[b,s,e,c] / combine[b,s,e,c]
        dispatch = jnp.einsum("bske,bskc->bsec", sel * keep[..., None], pos_oh)
        combine = jnp.einsum("bske,bskc->bsec",
                             sel * (topv * keep)[..., None], pos_oh)

        xin = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(x.dtype), x)
        xin = xin.reshape(e, b * c, d)                           # (E,B·C,D)

    with jax.named_scope("moe_experts"):
        h = common.linear_apply(p["up"], xin, specs.up, ctx)
        act = common.activation(specs.act)
        if specs.gated:
            g, u = jnp.split(h, 2, axis=-1)
            h = common.gated(act, g, u, x.dtype)
        else:
            h = act(h.astype(jnp.float32)).astype(x.dtype)
        h = common.linear_apply(p["down"], h, specs.down, ctx)

    with jax.named_scope("moe_combine"):
        h = h.reshape(e, b, c, d)
        y = jnp.einsum("ebcd,bsec->bsd", h, combine.astype(x.dtype))

    if specs.shared is not None:
        with jax.named_scope("moe_shared"):
            y = y + ffn.ffn_apply(p["shared"], x, specs.shared, ctx)

    # aux side-car: load-balancing loss term (Switch-style) + routing stats
    density = jnp.mean(jnp.sum(sel, axis=2), axis=(0, 1))        # (E,) token frac
    router_prob = jnp.mean(gates, axis=(0, 1))                   # (E,)
    kept = sel * keep[..., None]                                 # (B,S,K,E)
    aux = {
        "loss": e * jnp.sum(density * router_prob),
        "expert_tokens": jnp.sum(kept, axis=(0, 1, 2)).astype(jnp.int32),
        "dropped": jnp.sum(1.0 - keep).astype(jnp.int32),
        "rows": e * b * c,
    }
    return y, aux
