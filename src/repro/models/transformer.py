"""Model assembly: decoder-only LMs (dense/MoE/SSM/hybrid/VLM) and the
whisper encoder-decoder, from the block library.

Structure (DESIGN.md §3): layer 0 and layer n-1 are *unrolled* and get the
policy's first/last precision (the paper's mixed-precision recipe); the
middle layers are scanned in whole block-pattern periods (`lax.scan` over
stacked params — the compile-time analogue of BrainTTA's hardware loop
buffer), any remainder layers are unrolled.

Each block is pre-norm residual: x += mixer(norm(x)); x += ffn(norm(x)).
Mixer kinds: attn | local (sliding-window) | slstm | mlstm | rglru.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import qlinear
from repro.core.precision import get_policy

from . import attention, common, ffn, moe, rglru, ssm
from .common import ModelCtx

# ---------------------------------------------------------------------------
# one block
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockSpecs:
    kind: str
    mixer: Any
    ffn: Any = None        # FFNSpecs | MoESpecs | None
    is_moe: bool = False
    cross: bool = False    # whisper decoder block


def block_specs(cfg: ArchConfig, pol, kind: str, *, first=False, last=False,
                cross=False) -> BlockSpecs:
    if kind in ("attn", "local"):
        mix = attention.attn_specs(cfg, pol, first=first, last=last, cross=cross)
    elif kind == "mlstm":
        mix = ssm.mlstm_specs(cfg, pol, first=first, last=last)
    elif kind == "slstm":
        mix = ssm.slstm_specs(cfg, pol, first=first, last=last)
    elif kind == "rglru":
        mix = rglru.rglru_specs(cfg, pol, first=first, last=last)
    else:
        raise ValueError(kind)
    f = None
    is_moe = False
    if kind in ("attn", "local", "rglru") and cfg.d_ff > 0:
        if cfg.n_experts:
            f = moe.moe_specs(cfg, pol, first=first, last=last)
            is_moe = True
        else:
            f = ffn.ffn_specs(cfg, pol, first=first, last=last)
    return BlockSpecs(kind, mix, f, is_moe, cross)


def block_init(rng, cfg: ArchConfig, bs: BlockSpecs, dtype):
    ks = jax.random.split(rng, 4)
    p: dict[str, Any] = {"norm1": common.norm_init(cfg.d_model, cfg.norm, dtype)}
    if bs.kind in ("attn", "local"):
        p["mixer"] = attention.attn_init(ks[0], cfg, bs.mixer, dtype)
    elif bs.kind == "mlstm":
        p["mixer"] = ssm.mlstm_init(ks[0], cfg, bs.mixer, dtype)
    elif bs.kind == "slstm":
        p["mixer"] = ssm.slstm_init(ks[0], cfg, bs.mixer, dtype)
    elif bs.kind == "rglru":
        p["mixer"] = rglru.rglru_init(ks[0], cfg, bs.mixer, dtype)
    if bs.cross:
        p["norm_cross"] = common.norm_init(cfg.d_model, cfg.norm, dtype)
    if bs.ffn is not None:
        p["norm2"] = common.norm_init(cfg.d_model, cfg.norm, dtype)
        p["ffn"] = (moe.moe_init(ks[1], bs.ffn, dtype) if bs.is_moe
                    else ffn.ffn_init(ks[1], bs.ffn, dtype))
    return p


def _mixer_window(cfg: ArchConfig, kind: str) -> int:
    return cfg.window if kind == "local" else 0


def block_apply(p, x, bs: BlockSpecs, cfg: ArchConfig, ctx: ModelCtx, *,
                enc_out=None, causal=True):
    """Train/prefill-without-cache path. Returns (x, aux_loss)."""
    aux = jnp.float32(0.0)
    h = common.norm_apply(p["norm1"], x, cfg.norm)
    if bs.kind in ("attn", "local"):
        m = attention.attn_apply(p["mixer"], h, bs.mixer, cfg, ctx, causal=causal,
                                 window=_mixer_window(cfg, bs.kind))
    elif bs.kind == "mlstm":
        m = ssm.mlstm_apply(p["mixer"], h, bs.mixer, ctx, impl=cfg.mlstm_impl)
    elif bs.kind == "slstm":
        m = ssm.slstm_apply(p["mixer"], h, bs.mixer, ctx)
    else:
        m = rglru.rglru_apply(p["mixer"], h, bs.mixer, ctx)
    x = common.residual(x, m)
    if bs.cross and enc_out is not None:
        k, v = attention.cross_kv(p["mixer"], enc_out, bs.mixer, cfg, ctx)
        hc = common.norm_apply(p["norm_cross"], x, cfg.norm)
        x = common.residual(x, attention.cross_attn_apply(
            p["mixer"], hc, (k, v), bs.mixer, cfg, ctx))
    if bs.ffn is not None:
        h2 = common.norm_apply(p["norm2"], x, cfg.norm)
        if bs.is_moe:
            y, a = moe.moe_apply(p["ffn"], h2, bs.ffn, ctx)
            aux = aux + a["loss"]
        else:
            y = ffn.ffn_apply(p["ffn"], h2, bs.ffn, ctx)
        x = common.residual(x, y)
    return x, aux


def _moe_ffn(p, x, bs: BlockSpecs, cfg: ArchConfig, ctx: ModelCtx):
    """Shared serve-path FFN tail: residual add + optional MoE stats.

    Returns (x, st) where st is the block's routing-stat dict
    ({"expert_tokens": (E,) i32, "dropped": i32, "rows": i32}) iff this is
    an MoE block and ctx.moe_stats is on, else None — the top-level entry
    points sum the dicts across blocks (None is the empty contribution, so
    dense blocks in a mixed pattern keep the scan carry structure
    constant)."""
    st = None
    if bs.ffn is None:
        return x, st
    h2 = common.norm_apply(p["norm2"], x, cfg.norm)
    if bs.is_moe:
        y, a = moe.moe_apply(p["ffn"], h2, bs.ffn, ctx)
        if ctx.moe_stats:
            st = {"expert_tokens": a["expert_tokens"], "dropped": a["dropped"],
                  "rows": jnp.int32(a["rows"])}
    else:
        y = ffn.ffn_apply(p["ffn"], h2, bs.ffn, ctx)
    return common.residual(x, y), st


def _moe_zero(cfg: ArchConfig):
    """Zero routing-stat accumulator — the scan-carry seed when stats are on."""
    return {"expert_tokens": jnp.zeros((cfg.n_experts,), jnp.int32),
            "dropped": jnp.int32(0), "rows": jnp.int32(0)}


def _moe_add(tot, st):
    if tot is None or st is None:
        return tot
    return jax.tree.map(lambda a, b: a + b, tot, st)


def block_cache_shapes(cfg: ArchConfig, bs: BlockSpecs, batch: int, seq_len: int,
                       paged: tuple[int, int] | None = None, kv_dtype=None):
    if bs.kind in ("attn", "local"):
        c = attention.init_cache_shapes(cfg, batch, seq_len,
                                        _mixer_window(cfg, bs.kind),
                                        dtype=kv_dtype, paged=paged)
    elif bs.kind == "mlstm":
        c = ssm.mlstm_state_shapes(cfg, batch)
    elif bs.kind == "slstm":
        c = ssm.slstm_state_shapes(cfg, batch)
    else:
        c = rglru.rglru_state_shapes(cfg, batch)
    if bs.cross:
        hk, dh = cfg.n_kv_heads, cfg.head_dim
        shp = (batch, cfg.frontend_len, hk, dh)
        c["cross_k"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
        c["cross_v"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
    return c


def block_prefill(p, x, bs: BlockSpecs, cfg: ArchConfig, ctx: ModelCtx, *,
                  enc_out=None, cache_len: int = 0):
    """Prefill: like block_apply but returns the decode cache.

    Returns (x, cache, st) — st per `_moe_ffn` (None unless an MoE block
    under ctx.moe_stats)."""
    h = common.norm_apply(p["norm1"], x, cfg.norm)
    cache = {}
    if bs.kind in ("attn", "local"):
        m, cache = attention.attn_apply(
            p["mixer"], h, bs.mixer, cfg, ctx, causal=True,
            window=_mixer_window(cfg, bs.kind), return_cache=True,
            cache_len=cache_len)
        x = common.residual(x, m)
    else:
        # recurrent mixers: run full sequence then recompute final state via
        # one-step decode chain is wasteful; instead run the scan and capture
        # the final state by replaying decode on the last token only after
        # processing prefix — implemented as scan-with-final-state below.
        x_new, cache = _recurrent_prefill(p["mixer"], h, bs, cfg, ctx)
        x = common.residual(x, x_new)
    if bs.cross and enc_out is not None:
        k, v = attention.cross_kv(p["mixer"], enc_out, bs.mixer, cfg, ctx)
        cache["cross_k"], cache["cross_v"] = k, v
        hc = common.norm_apply(p["norm_cross"], x, cfg.norm)
        x = common.residual(x, attention.cross_attn_apply(
            p["mixer"], hc, (k, v), bs.mixer, cfg, ctx))
    x, st = _moe_ffn(p, x, bs, cfg, ctx)
    return x, cache, st


def _recurrent_prefill(pm, h, bs: BlockSpecs, cfg: ArchConfig, ctx: ModelCtx):
    """Run a recurrent mixer over the prefix and also return its final state.

    Baseline implementation: step the decode cell over time with lax.scan —
    sequential but state-exact. (rglru's parallel apply is used for train;
    prefill needs the state, so we scan the cell.)
    """
    b, t, _ = h.shape
    if bs.kind == "rglru" and not cfg.seq_prefill:
        # parallel prefill (§Perf A): associative scan + direct state extract
        return rglru.rglru_prefill(pm, h, bs.mixer, ctx)
    if bs.kind == "mlstm" and not cfg.seq_prefill:
        out = ssm.mlstm_prefill(pm, h, bs.mixer, ctx)
        if out is not None:            # chunkwise pass + final state (§Perf D)
            return out
    if bs.kind == "mlstm":
        shapes = ssm.mlstm_state_shapes(cfg, b, h.dtype)
        dec = functools.partial(ssm.mlstm_decode, specs=bs.mixer, ctx=ctx)
    elif bs.kind == "slstm":
        shapes = ssm.slstm_state_shapes(cfg, b)
        dec = functools.partial(ssm.slstm_decode, specs=bs.mixer, ctx=ctx)
    else:
        shapes = rglru.rglru_state_shapes(cfg, b, h.dtype)
        dec = functools.partial(rglru.rglru_decode, specs=bs.mixer, ctx=ctx)
    state0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    if bs.kind in ("mlstm",):
        state0["m"] = jnp.full_like(state0["m"], -1e30)
    if bs.kind == "slstm":
        state0["m"] = jnp.full_like(state0["m"], -1e30)

    def step(state, xt):
        y, state = dec(pm, xt[:, None], state)
        return state, y[:, 0]

    state, ys = jax.lax.scan(step, state0, jnp.moveaxis(h, 1, 0))
    return jnp.moveaxis(ys, 0, 1), state


def _pool_in_place(bs: BlockSpecs, cfg: ArchConfig, pages) -> bool:
    """Whether decode updates this block's KV in place: its cache is the
    paged pool of a full-attention layer (`attention.attn_decode`'s paged
    case). Window rings, recurrent states, contiguous slabs and cross-KV
    are not."""
    return (pages is not None and bs.kind in ("attn", "local")
            and not _mixer_window(cfg, bs.kind))


def block_decode(p, x, cache, pos, bs: BlockSpecs, cfg: ArchConfig, ctx: ModelCtx,
                 *, pages=None, layer=None):
    """One-token decode through a block. x: (B,1,D); pos: scalar or (B,).
    `layer`: index into stacked (L, ...) paged pools (`attn_decode`).

    Returns (x, cache, st) — st per `_moe_ffn`."""
    h = common.norm_apply(p["norm1"], x, cfg.norm)
    if bs.kind in ("attn", "local"):
        sub = {k: v for k, v in cache.items() if k in ("k", "v")}
        m, sub = attention.attn_decode(p["mixer"], h, sub, pos, bs.mixer, cfg, ctx,
                                       window=_mixer_window(cfg, bs.kind),
                                       pages=pages, layer=layer)
        cache = {**cache, **sub}
    elif bs.kind == "mlstm":
        m, cache2 = ssm.mlstm_decode(p["mixer"], h, cache, bs.mixer, ctx)
        cache = {**cache, **cache2}
    elif bs.kind == "slstm":
        m, cache2 = ssm.slstm_decode(p["mixer"], h, cache, bs.mixer, ctx)
        cache = {**cache, **cache2}
    else:
        m, cache2 = rglru.rglru_decode(p["mixer"], h, cache, bs.mixer, ctx)
        cache = {**cache, **cache2}
    x = common.residual(x, m)
    if bs.cross:
        hc = common.norm_apply(p["norm_cross"], x, cfg.norm)
        x = common.residual(x, attention.cross_attn_apply(
            p["mixer"], hc, (cache["cross_k"], cache["cross_v"]), bs.mixer,
            cfg, ctx))
    x, st = _moe_ffn(p, x, bs, cfg, ctx)
    return x, cache, st


def block_chunk(p, x, cache, pos0, bs: BlockSpecs, cfg: ArchConfig,
                ctx: ModelCtx, *, read_pages, write_pages, nreal):
    """Chunked-prefill through one block. x: (B, C, D); pos0: (B,).
    Returns (x, cache, st) — st per `_moe_ffn`.

    Only full-attention blocks are chunkable: window rings and recurrent
    states have no pageable representation of a partial prefix (the server
    falls back to whole-prompt prefill for those archs — `exact_prefill`).
    """
    if bs.kind != "attn":
        raise ValueError(f"chunked prefill requires attn blocks, got {bs.kind}")
    h = common.norm_apply(p["norm1"], x, cfg.norm)
    sub = {k: v for k, v in cache.items() if k in ("k", "v")}
    m, sub = attention.attn_prefill_chunk(
        p["mixer"], h, sub, pos0, bs.mixer, cfg, ctx,
        read_pages=read_pages, write_pages=write_pages, nreal=nreal)
    cache = {**cache, **sub}
    x = common.residual(x, m)
    x, st = _moe_ffn(p, x, bs, cfg, ctx)
    return x, cache, st


def block_pack(p, bs: BlockSpecs):
    """Train-layout block params -> packed serve layout."""
    out = {k: v for k, v in p.items() if k.startswith("norm")}
    m = p["mixer"]
    if bs.kind in ("attn", "local"):
        pm = {"qkv": qlinear.pack_params(m["qkv"], bs.mixer.qkv),
              "out": qlinear.pack_params(m["out"], bs.mixer.out)}
        if bs.cross:
            pm["cross_q"] = qlinear.pack_params(m["cross_q"], bs.mixer.cross_q)
            pm["cross_kv"] = qlinear.pack_params(m["cross_kv"], bs.mixer.cross_kv)
    elif bs.kind == "mlstm":
        pm = {"in_proj": qlinear.pack_params(m["in_proj"], bs.mixer.in_proj),
              "conv": m["conv"],
              "qkv": qlinear.pack_params(m["qkv"], bs.mixer.qkv),
              "gates": qlinear.pack_params(m["gates"], bs.mixer.gates),
              "out": qlinear.pack_params(m["out"], bs.mixer.out)}
    elif bs.kind == "slstm":
        pm = {"gates": qlinear.pack_params(m["gates"], bs.mixer.gates),
              "rec": m["rec"],
              "out": qlinear.pack_params(m["out"], bs.mixer.out)}
    else:
        pm = {"in_proj": qlinear.pack_params(m["in_proj"], bs.mixer.in_proj),
              "conv": m["conv"], "w_gates": m["w_gates"], "lam": m["lam"],
              "out": qlinear.pack_params(m["out"], bs.mixer.out)}
    out["mixer"] = pm
    if bs.ffn is not None:
        f = p["ffn"]
        if bs.is_moe:
            pf = {"router": qlinear.pack_params(f["router"], bs.ffn.router),
                  "up": qlinear.pack_params(f["up"], bs.ffn.up),
                  "down": qlinear.pack_params(f["down"], bs.ffn.down)}
            if "shared" in f:
                pf["shared"] = {
                    "up": qlinear.pack_params(f["shared"]["up"], bs.ffn.shared.up),
                    "down": qlinear.pack_params(f["shared"]["down"], bs.ffn.shared.down)}
        else:
            pf = {"up": qlinear.pack_params(f["up"], bs.ffn.up),
                  "down": qlinear.pack_params(f["down"], bs.ffn.down)}
        out["ffn"] = pf
    return out


# ---------------------------------------------------------------------------
# whole-model specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelSpecs:
    cfg: ArchConfig
    first: BlockSpecs
    mid: tuple[BlockSpecs, ...]       # one per pattern position (offset by 1)
    rem: tuple[BlockSpecs, ...]
    last: BlockSpecs
    n_periods: int
    embed_dim: int
    lm_head: Any
    encoder: tuple[BlockSpecs, ...] = ()


def build_specs(cfg: ArchConfig) -> ModelSpecs:
    pol = get_policy(cfg.policy)
    n, P = cfg.n_layers, len(cfg.block_pattern)
    cross = cfg.is_encdec
    if n < 2:
        raise ValueError("need >= 2 layers")
    n_mid = n - 2
    n_periods = n_mid // P if cfg.scan_layers else 0
    n_rem = n_mid - n_periods * P
    first = block_specs(cfg, pol, cfg.pattern_at(0), first=True, cross=cross)
    mid = tuple(block_specs(cfg, pol, cfg.pattern_at(1 + t), cross=cross)
                for t in range(P)) if n_periods else ()
    rem = tuple(block_specs(cfg, pol, cfg.pattern_at(1 + n_periods * P + t), cross=cross)
                for t in range(n_rem))
    last = block_specs(cfg, pol, cfg.pattern_at(n - 1), last=True, cross=cross)
    # lm_head is column-parallel under serve TP: vocab-sharded logits, no
    # collective (argmax over the sharded vocab axis is exact)
    lm_head = common.lspec(pol, "lm_head", cfg.d_model, cfg.vocab, last=True,
                           parallel="column")
    encoder = tuple(block_specs(cfg, pol, "attn") for _ in range(cfg.encoder_layers))
    return ModelSpecs(cfg, first, mid, rem, last, n_periods, cfg.d_model,
                      lm_head, encoder)


def _init_outer(keys, cfg: ArchConfig, sp: ModelSpecs, dtype) -> dict:
    """Train-layout parameters of everything but the scanned periods."""
    p: dict[str, Any] = {
        "embed": common.embed_init(keys[0], cfg.vocab, cfg.d_model, dtype),
        "first": block_init(keys[1], cfg, sp.first, dtype),
        "last": block_init(keys[2], cfg, sp.last, dtype),
        "final_norm": common.norm_init(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = common.linear_init(keys[3], sp.lm_head, dtype)
    for t, bs in enumerate(sp.rem):
        p[f"rem{t}"] = block_init(jax.random.fold_in(keys[5], t), cfg, bs, dtype)
    for t, bs in enumerate(sp.encoder):
        p[f"enc{t}"] = block_init(jax.random.fold_in(keys[6], t), cfg, bs, dtype)
    if sp.encoder:
        p["enc_norm"] = common.norm_init(cfg.d_model, cfg.norm, dtype)
    return p


def _init_period(k, cfg: ArchConfig, sp: ModelSpecs, dtype) -> dict:
    kk = jax.random.split(k, len(sp.mid))
    return {f"b{t}": block_init(kk[t], cfg, sp.mid[t], dtype)
            for t in range(len(sp.mid))}


def init(rng, cfg: ArchConfig) -> dict:
    """Train-layout parameters."""
    sp, dtype = build_specs(cfg), jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(rng, 8)
    p = _init_outer(keys, cfg, sp, dtype)
    if sp.n_periods:
        p["mid"] = jax.vmap(lambda k: _init_period(k, cfg, sp, dtype))(
            jax.random.split(keys[4], sp.n_periods))
    return p


def _strip_plane_twins(t):
    if isinstance(t, dict):
        return {k: _strip_plane_twins(v)
                for k, v in t.items() if k != "w_planes"}
    return t


def _pack_outer(params: dict, sp: ModelSpecs) -> dict:
    out: dict[str, Any] = {
        "embed": {"w": params["embed"]["w"].astype(jnp.bfloat16)},
        "first": block_pack(params["first"], sp.first),
        "last": block_pack(params["last"], sp.last),
        "final_norm": params["final_norm"],
    }
    if "lm_head" in params:
        out["lm_head"] = qlinear.pack_params(params["lm_head"], sp.lm_head)
    for t, bs in enumerate(sp.rem):
        out[f"rem{t}"] = block_pack(params[f"rem{t}"], bs)
    for t, bs in enumerate(sp.encoder):
        out[f"enc{t}"] = block_pack(params[f"enc{t}"], bs)
    if sp.encoder:
        out["enc_norm"] = params["enc_norm"]
    return out


def _pack_period(period: dict, sp: ModelSpecs) -> dict:
    return {f"b{t}": block_pack(period[f"b{t}"], sp.mid[t])
            for t in range(len(sp.mid))}


def pack_for_serve(params: dict, cfg: ArchConfig, *,
                   plane_twins: bool = False) -> dict:
    """Convert train-layout params to the packed serve layout (bit-planes).

    `plane_twins=True` keeps the stacked bit-plane twin (`w_planes`) that
    `qlinear.pack_params` emits next to the direct int4/int8 layout — the
    `impl="planes"` cells and the `--spec-draft` truncated-plane draft read
    it. The default strips it: the twin duplicates those layers' weight
    bytes, and the paper's packed-footprint ladder (binary < ternary < int8
    < none) is a claim about the serving layout, not the plane machinery.
    """
    sp = build_specs(cfg)
    out = _pack_outer(params, sp)
    if sp.n_periods:
        out["mid"] = jax.vmap(lambda pr: _pack_period(pr, sp))(params["mid"])
    return out if plane_twins else _strip_plane_twins(out)


def init_packed(rng, cfg: ArchConfig, *, plane_twins: bool = False,
                constrain=lambda tree: tree) -> dict:
    """`pack_for_serve(init(rng, cfg), cfg)` without ever holding the whole
    training layout: each scanned period is drawn and packed inside one
    `lax.map` step, so only one period's training-layout weights (and its
    f32 packing temporaries) are live at a time. Same keys and formulas;
    the scanned periods compile to a different program than the vmapped
    ones, so their floats may differ in the last ulp (and a code on a
    rounding boundary may flip). `constrain` is applied to each
    training-layout tree before it is packed — the serve mesh pins it to
    the sharding rules there."""
    sp, dtype = build_specs(cfg), jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(rng, 8)
    out = _pack_outer(constrain(_init_outer(keys, cfg, sp, dtype)), sp)
    if sp.n_periods:
        out["mid"] = jax.lax.map(
            lambda k: _pack_period(
                constrain(_init_period(k, cfg, sp, dtype)), sp),
            jax.random.split(keys[4], sp.n_periods))
    return out if plane_twins else _strip_plane_twins(out)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _encode(params, sp: ModelSpecs, audio_embeds, ctx: ModelCtx):
    cfg = sp.cfg
    x = audio_embeds.astype(ctx.dtype)
    x = x + common.sinusoidal_positions(x.shape[1], cfg.d_model).astype(ctx.dtype)
    for t, bs in enumerate(sp.encoder):
        x, _ = block_apply(params[f"enc{t}"], x, bs, cfg, ctx, causal=False)
    return common.norm_apply(params["enc_norm"], x, cfg.norm)


def _stack_apply(params, x, sp: ModelSpecs, ctx: ModelCtx, *, enc_out=None):
    """first -> scanned periods -> remainder -> last. Returns (x, aux)."""
    cfg = sp.cfg
    sa = lambda t: common.shard_act(t, ctx)
    x, aux = block_apply(params["first"], sa(x), sp.first, cfg, ctx, enc_out=enc_out)

    if sp.n_periods:
        def period(xc, pp):
            xx, a = xc
            for t, bs in enumerate(sp.mid):
                xx2, a2 = block_apply(pp[f"b{t}"], sa(xx), bs, cfg, ctx, enc_out=enc_out)
                xx, a = sa(xx2), a + a2
            return (xx, a), None
        # remat policy: recompute activations but SAVE the gathered quantized
        # weights (tiny per period; re-gathering them in bwd recompute was
        # ~3x the FSDP gather volume — §Perf B iter-6)
        body = jax.checkpoint(
            period,
            policy=jax.checkpoint_policies.save_only_these_names("qweight"),
        ) if cfg.remat else period
        (x, aux), _ = jax.lax.scan(body, (x, aux), params["mid"])

    for t, bs in enumerate(sp.rem):
        x, a = block_apply(params[f"rem{t}"], sa(x), bs, cfg, ctx, enc_out=enc_out)
        aux = aux + a
    x, a = block_apply(params["last"], sa(x), sp.last, cfg, ctx, enc_out=enc_out)
    return common.shard_act(x, ctx), aux + a


def _logits(params, x, sp: ModelSpecs, ctx: ModelCtx):
    x = common.norm_apply(params["final_norm"], x, sp.cfg.norm)
    if sp.cfg.tie_embeddings:
        return (x @ params["embed"]["w"].astype(x.dtype).T).astype(jnp.float32)
    return common.linear_apply(params["lm_head"], x, sp.lm_head, ctx).astype(jnp.float32)


def forward(params, tokens, sp: ModelSpecs, ctx: ModelCtx, *,
            frontend_embeds=None):
    """Teacher-forcing forward. tokens: (B, T) -> logits (B, T(+Np), V), aux.

    VLM: frontend_embeds (B, Np, D) are prepended (loss masking is the
    caller's job via the returned prefix length).
    Audio (enc-dec): frontend_embeds (B, Tenc, D) go through the encoder.
    """
    cfg = sp.cfg
    x = common.shard_act(common.embed_apply(params["embed"], tokens, ctx.dtype), ctx)
    enc_out = None
    prefix = 0
    if cfg.is_encdec and frontend_embeds is not None:
        enc_out = _encode(params, sp, frontend_embeds, ctx)
    elif cfg.frontend == "vision" and frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(ctx.dtype), x], axis=1)
        prefix = frontend_embeds.shape[1]
    x, aux = _stack_apply(params, x, sp, ctx, enc_out=enc_out)
    return _logits(params, x, sp, ctx), aux, prefix


def loss_fn(params, batch, sp: ModelSpecs, ctx: ModelCtx):
    """Cross-entropy next-token loss. batch: {tokens, targets[, frontend]}"""
    logits, aux, prefix = forward(params, batch["tokens"], sp, ctx,
                                  frontend_embeds=batch.get("frontend"))
    if prefix:
        logits = logits[:, prefix:]
    loss = common.cross_entropy(logits, batch["targets"])
    return loss + 0.01 * aux, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------

def cache_shapes(cfg: ArchConfig, batch: int, seq_len: int,
                 paged: tuple[int, int] | None = None, kv_dtype=None):
    """Decode-cache ShapeDtypeStructs. `paged=(num_pages, page_size)` puts
    full-attention KV into the shared block pool (see launch/kv_cache.py);
    window rings and recurrent states stay per-slot slabs.

    `kv_dtype` overrides the attention KV storage dtype (None =>
    cfg.kv_cache_dtype). The serve loop passes its compute dtype so the pool
    matches what `attn_apply`/`attn_decode` actually store — prefill caches
    follow the compute dtype unless the int8-requant cache is on.
    """
    sp = build_specs(cfg)
    shapes: dict[str, Any] = {
        "first": block_cache_shapes(cfg, sp.first, batch, seq_len, paged, kv_dtype),
        "last": block_cache_shapes(cfg, sp.last, batch, seq_len, paged, kv_dtype),
    }
    if sp.n_periods:
        def stack(tree):
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((sp.n_periods,) + s.shape, s.dtype), tree)
        shapes["mid"] = stack({f"b{t}": block_cache_shapes(cfg, bs, batch, seq_len,
                                                           paged, kv_dtype)
                               for t, bs in enumerate(sp.mid)})
    for t, bs in enumerate(sp.rem):
        shapes[f"rem{t}"] = block_cache_shapes(cfg, bs, batch, seq_len, paged,
                                               kv_dtype)
    return shapes


def init_cache(cfg: ArchConfig, batch: int, seq_len: int,
               paged: tuple[int, int] | None = None, kv_dtype=None):
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         cache_shapes(cfg, batch, seq_len, paged, kv_dtype))
    return _fix_m_states(cache, cfg)


def _fix_m_states(cache, cfg):
    """m-stabilizer states start at -inf (see ssm.py)."""
    def fix(path, leaf):
        names = [getattr(k, "key", "") for k in path]
        if names and names[-1] == "m":
            return jnp.full_like(leaf, -1e30)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


def prefill(params, tokens, sp: ModelSpecs, ctx: ModelCtx, *, frontend_embeds=None,
            cache_len: int = 0, last_pos=None):
    """Process the prompt, return (last-position logits, cache).

    `cache_len`: KV-cache capacity to allocate (0 => prompt length; pass
    prompt_len + max_new_tokens for generation).
    `last_pos`: (B,) index of each row's final *real* token when `tokens` is
    right-padded to a bucket length (continuous-batching prefill); None =>
    the literal last column. Causal masking keeps real positions from
    attending to the padding, so the cache below `last_pos` is unaffected.

    Under ctx.moe_stats (MoE archs), returns (logits, cache, moe_stats) —
    the per-block routing counters summed over the stack. NOTE: prefill
    routes padding rows too, so expert_tokens/dropped include bucket-padding
    traffic (same for the sequential oracle — counters stay comparable).
    """
    cfg = sp.cfg
    x = common.shard_act(common.embed_apply(params["embed"], tokens, ctx.dtype), ctx)
    enc_out = None
    if cfg.is_encdec and frontend_embeds is not None:
        enc_out = _encode(params, sp, frontend_embeds, ctx)
    elif cfg.frontend == "vision" and frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(ctx.dtype), x], axis=1)
    cache_len = cache_len or x.shape[1]

    moe_tot = _moe_zero(cfg) if (ctx.moe_stats and cfg.n_experts) else None
    caches: dict[str, Any] = {}
    x, caches["first"], st = block_prefill(params["first"], x, sp.first, cfg, ctx,
                                           enc_out=enc_out, cache_len=cache_len)
    moe_tot = _moe_add(moe_tot, st)
    if sp.n_periods:
        def period(carry, pp):
            xx, tot = carry
            cs = {}
            for t, bs in enumerate(sp.mid):
                xx, cs[f"b{t}"], st = block_prefill(pp[f"b{t}"], xx, bs, cfg, ctx,
                                                    enc_out=enc_out,
                                                    cache_len=cache_len)
                tot = _moe_add(tot, st)
            return (xx, tot), cs
        (x, moe_tot), caches["mid"] = jax.lax.scan(period, (x, moe_tot),
                                                   params["mid"])
    for t, bs in enumerate(sp.rem):
        x, caches[f"rem{t}"], st = block_prefill(params[f"rem{t}"], x, bs, cfg, ctx,
                                                 enc_out=enc_out, cache_len=cache_len)
        moe_tot = _moe_add(moe_tot, st)
    x, caches["last"], st = block_prefill(params["last"], x, sp.last, cfg, ctx,
                                          enc_out=enc_out, cache_len=cache_len)
    moe_tot = _moe_add(moe_tot, st)
    if last_pos is None:
        x_last = x[:, -1:]
    else:
        idx = jnp.asarray(last_pos, jnp.int32).reshape(-1, 1, 1)
        x_last = jnp.take_along_axis(x, idx, axis=1)
    logits = _logits(params, x_last, sp, ctx)
    if ctx.moe_stats:
        return logits, caches, moe_tot
    return logits, caches


def _chunk_stack(params, cache, tokens, pos0, sp: ModelSpecs, ctx: ModelCtx,
                 kw):
    """Shared multi-token paged traversal: embed `tokens` (B, C) and run the
    chunk path (attention reads prior pool KV + the chunk's own causal
    prefix, writes the chunk KV through `write_pages`) through every block.
    Returns (hidden (B, C, D), new_cache, moe_tot) — moe_tot per the
    ctx.moe_stats contract (None when off). Backs both `prefill_chunk`
    (chunked prompt prefill) and `decode_verify` (speculative multi-token
    verification) — one algebra, two logits policies."""
    cfg = sp.cfg
    x = common.shard_act(common.embed_apply(params["embed"], tokens, ctx.dtype), ctx)
    moe_tot = _moe_zero(cfg) if (ctx.moe_stats and cfg.n_experts) else None
    new_cache: dict[str, Any] = {}
    x, new_cache["first"], st = block_chunk(params["first"], x, cache["first"], pos0,
                                            sp.first, cfg, ctx, **kw)
    moe_tot = _moe_add(moe_tot, st)
    if sp.n_periods:
        def period(carry, scanned):
            xx, tot = carry
            pp, cc = scanned
            ncs = {}
            for t, bs in enumerate(sp.mid):
                xx, ncs[f"b{t}"], st = block_chunk(pp[f"b{t}"], xx, cc[f"b{t}"],
                                                   pos0, bs, cfg, ctx, **kw)
                tot = _moe_add(tot, st)
            return (xx, tot), ncs
        (x, moe_tot), new_cache["mid"] = jax.lax.scan(
            period, (x, moe_tot), (params["mid"], cache["mid"]))
    for t, bs in enumerate(sp.rem):
        x, new_cache[f"rem{t}"], st = block_chunk(params[f"rem{t}"], x,
                                                  cache[f"rem{t}"], pos0, bs,
                                                  cfg, ctx, **kw)
        moe_tot = _moe_add(moe_tot, st)
    x, new_cache["last"], st = block_chunk(params["last"], x, cache["last"], pos0,
                                           sp.last, cfg, ctx, **kw)
    moe_tot = _moe_add(moe_tot, st)
    return x, new_cache, moe_tot


def prefill_chunk(params, cache, tokens, pos0, sp: ModelSpecs, ctx: ModelCtx, *,
                  read_pages, write_pages, nreal, last_idx):
    """One prompt *chunk* through the stack against the paged cache.

    tokens: (B, C) — C chunk tokens starting at absolute position pos0 (B,),
    right-padded past `nreal` (B,). read_pages/write_pages: (B, max_pages)
    page rows (write row has NULL_PAGE at shared-prefix pages). Returns
    (logits, cache) where logits (B, 1, V) are taken at chunk-local index
    `last_idx` (B,) — only meaningful on the final chunk of a prompt, where
    the server points it at the prompt's last token to sample the first
    output (garbage otherwise, ignored by the caller).

    Byte-exactness: each chunk writes exactly the KV bytes whole-prompt
    `prefill` would (see attention.attn_prefill_chunk), and the final chunk's
    last-row hidden state is bit-identical to whole-prompt `last_pos` gather,
    so the sampled first token matches the sequential oracle.
    """
    kw = dict(read_pages=read_pages, write_pages=write_pages, nreal=nreal)
    x, new_cache, moe_tot = _chunk_stack(params, cache, tokens, pos0, sp, ctx, kw)
    idx = jnp.asarray(last_idx, jnp.int32).reshape(-1, 1, 1)
    x_last = jnp.take_along_axis(x, idx, axis=1)
    logits = _logits(params, x_last, sp, ctx)
    if ctx.moe_stats:
        return logits, new_cache, moe_tot
    return logits, new_cache


def decode_verify(params, cache, tokens, pos0, sp: ModelSpecs, ctx: ModelCtx, *,
                  read_pages, write_pages, nreal):
    """Full-precision multi-token VERIFY step for self-speculative decoding.

    tokens: (B, K) — row b is [last accepted token, draft_0, .., draft_{K-2}]
    at absolute positions pos0[b] .. pos0[b]+K-1, right-padded past nreal[b]
    (slots verifying fewer than K tokens this tick). Same chunk algebra as
    `prefill_chunk` — causal attention over prior pool KV plus the chunk's
    own prefix, KV scattered through `write_pages` — but logits are returned
    for EVERY chunk row (B, K, V): row i is the exact next-token distribution
    after consuming tokens[:, :i+1], i.e. what sequential `decode_step` would
    produce at position pos0+i. The server samples each row with the same
    stateless (seed, index) rng as sequential decode and accepts the longest
    draft prefix that matches — so speculative serving stays token-exact.

    KV written for rows past the accepted prefix is garbage from rejected
    draft inputs; it is harmless because every future decode write lands at
    the slot's (rewound) position before any read reaches it, and the
    scheduler forks shared pages across the whole [pos0, pos0+K) write range
    before dispatch (see launch/serve.py `_spec_tick`).
    """
    kw = dict(read_pages=read_pages, write_pages=write_pages, nreal=nreal)
    x, new_cache, moe_tot = _chunk_stack(params, cache, tokens, pos0, sp, ctx, kw)
    logits = _logits(params, x, sp, ctx)
    if ctx.moe_stats:
        return logits, new_cache, moe_tot
    return logits, new_cache


def decode_step(params, cache, tokens, pos, sp: ModelSpecs, ctx: ModelCtx, *,
                pages=None, trace_counts: dict | None = None):
    """One decode step. tokens: (B, 1); pos: scalar int32 (aligned decode) or
    (B,) int32 — one position per slot (continuous batching).

    `pages`: (B, max_pages) int32 page table when the cache was built with
    `init_cache(..., paged=(num_pages, page_size))`; full-attention layers
    then write/read through the page lists (see launch/kv_cache.py).

    In place: a scanned block whose cache is the paged pool
    (`_pool_in_place`) carries its stacked (n_periods, num_pages, P, Hk, dh)
    K and V through the layer scan, and each layer writes its token at
    [layer, pid, off] and reads its layer of the whole pool. As scan
    `xs`/`ys` each layer would slice its whole pool out and write it back
    for one token. Every other cache (rings, recurrent state, slabs,
    cross-KV) stays `xs`/`ys`. With the cache donated (the server's decode
    program) the pool is never copied. `trace_counts`, when given, gets
    "inplace_layers" at trace time: the layers updated that way.

    This is the `serve_step` the decode_* dry-run shapes lower. Under
    ctx.moe_stats, returns (logits, cache, moe_stats) — counters include the
    padding/parked slots in the batch (they decode like real slots; the
    oracle pads identically, so comparisons stay exact).
    """
    cfg = sp.cfg
    x = common.shard_act(common.embed_apply(params["embed"], tokens, ctx.dtype), ctx)
    moe_tot = _moe_zero(cfg) if (ctx.moe_stats and cfg.n_experts) else None
    new_cache: dict[str, Any] = {}
    x, new_cache["first"], st = block_decode(params["first"], x, cache["first"], pos,
                                             sp.first, cfg, ctx, pages=pages)
    moe_tot = _moe_add(moe_tot, st)
    inplace = sum(_pool_in_place(bs, cfg, pages)
                  for bs in (sp.first, *sp.rem, sp.last))
    if sp.n_periods:
        carried = {f"b{t}" for t, bs in enumerate(sp.mid)
                   if _pool_in_place(bs, cfg, pages)}
        inplace += sp.n_periods * len(carried)
        pools = {b: {n: cache["mid"][b][n] for n in ("k", "v")}
                 for b in carried}
        rest = {b: {n: leaf for n, leaf in c.items()
                    if b not in carried or n not in ("k", "v")}
                for b, c in cache["mid"].items()}

        def period(carry, scanned):
            xx, tot, pools = carry
            pp, cc, i = scanned
            pools, ncs = dict(pools), {}
            for t, bs in enumerate(sp.mid):
                b = f"b{t}"
                xx, c, st = block_decode(
                    pp[b], xx, {**cc[b], **pools.get(b, {})}, pos, bs, cfg,
                    ctx, pages=pages, layer=i if b in carried else None)
                if b in carried:
                    pools[b] = {n: c.pop(n) for n in ("k", "v")}
                ncs[b] = c
                tot = _moe_add(tot, st)
            return (xx, tot, pools), ncs
        (x, moe_tot, pools), ys = jax.lax.scan(
            period, (x, moe_tot, pools),
            (params["mid"], rest, jnp.arange(sp.n_periods, dtype=jnp.int32)))
        new_cache["mid"] = {b: {**c, **pools.get(b, {})} for b, c in ys.items()}
    for t, bs in enumerate(sp.rem):
        x, new_cache[f"rem{t}"], st = block_decode(params[f"rem{t}"], x,
                                                   cache[f"rem{t}"], pos, bs,
                                                   cfg, ctx, pages=pages)
        moe_tot = _moe_add(moe_tot, st)
    x, new_cache["last"], st = block_decode(params["last"], x, cache["last"], pos,
                                            sp.last, cfg, ctx, pages=pages)
    moe_tot = _moe_add(moe_tot, st)
    if trace_counts is not None:
        trace_counts["inplace_layers"] = inplace
    logits = _logits(params, x, sp, ctx)
    if ctx.moe_stats:
        return logits, new_cache, moe_tot
    return logits, new_cache
