"""Step builders: the jitted train / prefill / decode steps with their
sharding trees. These are what the dry-run lowers and what train.py/serve.py
execute.

train_step = microbatched (lax.scan) grad accumulation -> AdamW update.
GSPMD inserts the TP/FSDP collectives from the param shardings; the pod axis
sees only gradient all-reduces (sharding.py). An optional int8-compressed
gradient all-reduce variant (shard_map manual over dp axes, auto over model)
is provided for non-FSDP configs — the §Perf collective lever.
"""
from __future__ import annotations

import functools
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, SHAPES, ShapeConfig
from repro.models import registry, transformer
from repro.models.common import ModelCtx
from repro.optim import adamw as adamw_mod
from repro.optim import compress
from repro.optim.adamw import adamw, apply_updates, cosine_schedule

from . import sharding
from .mesh import dp_axes


def make_optimizer(cfg: ArchConfig, *, peak_lr: float = 3e-4, warmup: int = 100,
                   total: int = 10_000):
    return adamw(cosine_schedule(peak_lr, warmup, total),
                 int8_state=cfg.opt_state_int8)


def make_train_step(cfg: ArchConfig, sp, opt, *, microbatches: int | None = None,
                    grad_compress: bool = False, ctx: ModelCtx | None = None,
                    grad_shardings=None):
    """Returns train_step(params, opt_state, batch, rng) -> (params, opt, metrics).

    The global batch is split into `microbatches` chunks accumulated with a
    lax.scan (bounds activation memory; DESIGN.md §3). `grad_shardings`
    (param-sharding tree) pins the per-microbatch gradients and the
    accumulator to the parameter layout, so each microbatch contributes via a
    reduce-scatter into the shard instead of a full all-reduce."""
    ctx = ctx or ModelCtx(mode="train")
    mb = microbatches or cfg.microbatches

    def loss_fn(params, batch):
        return transformer.loss_fn(params, batch, sp, ctx)

    def pin_grads(tree):
        if grad_shardings is None:
            return tree
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s), tree,
            grad_shardings)

    def train_step(params, opt_state, batch, rng):
        b = batch["tokens"].shape[0]
        assert b % mb == 0, (b, mb)

        def reshape_mb(x):
            return x.reshape(mb, b // mb, *x.shape[1:])
        mbatch = jax.tree.map(reshape_mb, batch)

        def mb_step(acc, mbx):
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mbx)
            grads = pin_grads(grads)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
            return pin_grads(acc), loss

        g0 = pin_grads(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                    params))
        grads, losses = jax.lax.scan(mb_step, g0, mbatch)
        grads = jax.tree.map(lambda g: g / mb, grads)
        if grad_compress:
            # int8-compressed DP all-reduce (params replicated over dp axes)
            grads = _compressed_dp_allreduce(grads, rng)
        updates, opt_state, om = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        metrics = {"loss": jnp.mean(losses), **om}
        return params, opt_state, metrics

    return train_step


def _compressed_dp_allreduce(grads, rng):
    """Placeholder hook replaced under shard_map in make_compressed_train_step;
    in the pure-pjit path GSPMD already reduced grads, so identity."""
    return grads


def make_compressed_train_step(cfg: ArchConfig, sp, opt, mesh: Mesh, *,
                               microbatches: int | None = None,
                               ctx: ModelCtx | None = None):
    """Beyond-paper variant: manual DP via shard_map with int8-compressed
    gradient all-reduce; 'model' axis left to GSPMD (auto). Params must be
    replicated over dp axes (no FSDP) — used for small/mid models where the
    collective term is gradient-bound."""
    ctx = ctx or ModelCtx(mode="train")
    mb = microbatches or cfg.microbatches
    dp = dp_axes(mesh)

    def loss_fn(params, batch):
        return transformer.loss_fn(params, batch, sp, ctx)

    def body(params, opt_state, batch, rng):
        b = batch["tokens"].shape[0]
        def reshape_mb(x):
            return x.reshape(mb, max(b // mb, 1), *x.shape[1:])
        mbatch = jax.tree.map(reshape_mb, batch)

        def mb_step(acc, mbx):
            (_, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mbx)
            loss, _ = loss_fn(params, mbx)
            return jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads), loss

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        grads, losses = jax.lax.scan(mb_step, g0, mbatch)
        n_dp = 1
        for a in dp:
            n_dp *= mesh.shape[a]
        grads = compress.compressed_psum(grads, dp, rng)      # int8 wire format
        grads = jax.tree.map(lambda g: g / (mb * n_dp), grads)
        updates, opt_state, om = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": jnp.mean(losses), **om}

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P(), P(tuple(dp)), P()),
                         out_specs=(P(), P(), P()), check_vma=False)


def make_prefill_step(cfg: ArchConfig, sp, *, ctx: ModelCtx | None = None):
    """Serve prefill; every quantized matmul goes through
    kernels.dispatch.qgemm with a per-layer OperatingPoint — precisions from
    the layer's policy assignment, formulation/backend/tune from ctx.

    NOTE: under ctx.moe_stats the transformer entry points return a third
    MoE routing-stats value (the serve driver's contract). The default ctx
    here leaves it off, so these step builders — and the dry-run cells that
    lower them via `jax.eval_shape(step, ...)[1]` — keep the 2-tuple shape."""
    ctx = ctx or ModelCtx(mode="serve")

    def prefill_step(params, batch):
        return transformer.prefill(params, batch["tokens"], sp, ctx,
                                   frontend_embeds=batch.get("frontend"))
    return prefill_step


def make_decode_step(cfg: ArchConfig, sp, *, ctx: ModelCtx | None = None):
    """Serve decode. When `batch` carries a "pages" entry (a
    (slots, max_pages) int32 page table), the paged-pool layout is lowered —
    the same fixed decode signature the continuous-batching server jits, so
    dry-run cells cost the real thing. Reads go through the PageTable
    indirection, which is what makes prefix-shared pages transparent to the
    model; the WRITE side relies on the scheduler's fork-before-write
    contract (launch/serve.py `_prepare_pages`): by the time this step runs,
    every page a slot writes is exclusively owned.

    The paged READ path is selected by ctx (threaded from the serve driver's
    --backend/--paged-attn/--tune flags): backend "pallas" (or
    paged_attn="fused") lowers the fused page-walk kernel
    (kernels.paged_attn.paged_flash_decode, its pages-per-block Tile from
    ctx.tune or the shipped TuneTable) in place of the jnp gather — both
    paths share the identical cache write and post-fork table, so swapping
    them never changes the decode signature or the CoW contract.

    Multi-tenant serving (launch/multi_serve.py) builds one of these per
    tenant — the signature is keyed by that tenant's (cfg, policy, ctx), so
    co-scheduled models never share a trace and the per-model --jit-budget
    accounting stays exact even though every tenant's pages live in the one
    shared pool."""
    ctx = ctx or ModelCtx(mode="serve")

    def decode_step(params, batch):
        return transformer.decode_step(params, batch["cache"], batch["tokens"],
                                       batch["pos"], sp, ctx,
                                       pages=batch.get("pages"))
    return decode_step


def make_chunk_step(cfg: ArchConfig, sp, *, ctx: ModelCtx | None = None):
    """Serve chunked-prefill step (transformer.prefill_chunk): one prompt
    chunk scattered/attended through the paged pool at a position offset —
    the piece the mixed prefill/decode server tick dispatches alongside
    `make_decode_step` so long prompts stop stalling the decode slots.

    `batch` carries tokens (B, C), pos0 (B,), read_pages/write_pages
    (B, max_pages), nreal (B,) and last_idx (B,) — all fixed shapes for a
    given chunk budget C, so chunked traffic compiles exactly one extra
    signature next to the decode step (the serve driver's --jit-budget
    accounting counts it under the "chunk" key)."""
    ctx = ctx or ModelCtx(mode="serve")

    def chunk_step(params, batch):
        return transformer.prefill_chunk(
            params, batch["cache"], batch["tokens"], batch["pos0"], sp, ctx,
            read_pages=batch["read_pages"], write_pages=batch["write_pages"],
            nreal=batch["nreal"], last_idx=batch["last_idx"])
    return chunk_step


# ---------------------------------------------------------------------------
# shape/sharding assembly for a (cfg, workload shape, mesh) cell
# ---------------------------------------------------------------------------

def abstract_train_state(cfg: ArchConfig, opt):
    """(params, opt_state) as ShapeDtypeStructs — no allocation."""
    params = jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0), cfg))
    opt_state = jax.eval_shape(lambda: opt.init(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params)))
    return params, opt_state


def abstract_serve_params(cfg: ArchConfig):
    return jax.eval_shape(lambda: transformer.pack_for_serve(
        transformer.init(jax.random.PRNGKey(0), cfg), cfg))


def act_dp_for(mesh: Mesh, per_step_batch: int) -> tuple | None:
    """dp axes to pin activations to, if they divide the batch."""
    dp = dp_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    return tuple(dp) if (n and per_step_batch % n == 0) else None


def cell_lowering_args(cfg: ArchConfig, shape: ShapeConfig | str, mesh: Mesh, *,
                       opt=None, fsdp: bool = True):
    """Everything jax.jit(...).lower(...) needs for one dry-run cell:
    (step_fn, arg ShapeDtypeStructs, in_shardings, out_shardings, donate)."""
    if isinstance(shape, str):
        shape = SHAPES[shape]
    sp = transformer.build_specs(cfg)
    inputs = registry.input_specs(cfg, shape)
    mb = cfg.microbatches if shape.kind == "train" else 1
    ctx = ModelCtx(mode="train" if shape.kind == "train" else "serve",
                   act_dp=act_dp_for(mesh, shape.global_batch // mb),
                   attn_cp="model" if shape.seq_len % mesh.shape["model"] == 0
                   else None,
                   fsdp_wire=cfg.fsdp_wire)

    if shape.kind == "train":
        opt = opt or make_optimizer(cfg)
        params, opt_state = abstract_train_state(cfg, opt)
        ps = sharding.param_shardings(mesh, params, fsdp=fsdp)
        step = make_train_step(cfg, sp, opt, ctx=ctx, grad_shardings=ps)
        os_ = sharding.opt_state_shardings(mesh, opt_state, ps)
        bs = sharding.batch_shardings(mesh, inputs, global_batch=shape.global_batch)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        args = (params, opt_state, inputs, rng)
        in_sh = (ps, os_, bs, NamedSharding(mesh, P()))
        out_sh = (ps, os_, None)
        return step, args, in_sh, out_sh, (0, 1)

    params = abstract_serve_params(cfg)
    # serve weights: TP over model, REPLICATED over dp — packed ternary/binary
    # weights are 8-32x smaller than bf16 (the BrainTTA point), so they fit
    # replicated; FSDP gathers per decoded token would drown the memory term.
    ps = sharding.param_shardings(mesh, params, fsdp=False)
    if shape.kind == "prefill":
        step = make_prefill_step(cfg, sp, ctx=ctx)
        bs = sharding.batch_shardings(mesh, inputs, global_batch=shape.global_batch)
        with mesh:   # shard_act constraints need the mesh context to trace
            out_cache = jax.eval_shape(step, params, inputs)[1]
        cache_out_sh = sharding.cache_shardings(mesh, out_cache,
                                                batch=shape.global_batch)
        return step, (params, inputs), (ps, bs), (None, cache_out_sh), ()
    # decode
    step = make_decode_step(cfg, sp, ctx=ctx)
    cache_sh = sharding.cache_shardings(mesh, inputs["cache"],
                                        batch=shape.global_batch)
    tok_sh = sharding.batch_shardings(
        mesh, {k: v for k, v in inputs.items() if k != "cache"},
        global_batch=shape.global_batch)
    bs = {**tok_sh, "cache": cache_sh}
    return step, (params, inputs), (ps, bs), (None, cache_sh), (1,)