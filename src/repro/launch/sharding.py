"""Sharding rules: param/cache/input PartitionSpecs from pytree paths.

Strategy (DESIGN.md §3):
  * TP over "model": column-parallel for qkv/up projections (out-dim), row-
    parallel for out/down projections (in-dim) — Megatron pairing, so one
    collective per block instead of two.
  * FSDP over "data": the non-TP weight dim is sharded over the data axis
    (ZeRO-3 via GSPMD; gathered per-layer under the scan).
  * EP over "model" for MoE expert stacks (leading E axis). Under serve
    (fsdp=False) this placement is exploited by compute: kernels.dispatch
    runs the grouped expert dispatch (`_ep_column`/`_ep_row`) whose
    shard_map in_specs are exactly these rules — each shard computes only
    its local experts. `ep_plan`'s whole-expert guard (E % model == 0) and
    `fit_spec`'s drop of non-dividing axes agree by construction: a config
    whose expert count the axis can't split replicates the stack here AND
    falls back to the dense expert vmap there (docs/MOE.md).
  * "pod" axis: pure DP — parameters are NOT sharded over pods (gathering
    weights over DCI every layer would drown; gradients all-reduce over pod
    instead).
  * int8 optimizer moments (shape-preserving codec) shard exactly like their
    parameter; per-block scales drop the sharded last-axis spec if blocking
    collapsed it.
  * batch-bearing tensors (inputs, caches, activations) shard batch over
    ("data",) [+"pod"], heads over "model" where present; batch=1 long-context
    decode falls back to replicated batch + model-sharded heads/state.

Everything is a *rule on the leaf path + shape*, applied with
jax.tree_util.tree_map_with_path — transparent, testable, no model changes.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import pack
from .mesh import dp_axes

def abstract_mesh(shape=(16, 16), axes=("data", "model")):
    """An AbstractMesh for rule evaluation — no devices needed."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))


# path components that mark a row-parallel linear (contraction dim sharded)
_ROW_PARALLEL = {"out", "down"}
# leaf names of packed weight tensors (K packed along the last axis; the
# per-leaf pack factor — 32-operand bit-plane words, 8-nibble s4 words —
# lives in core.pack.K_QUANTUM, shared with kernels.dispatch.tp_plan)
_PACKED = frozenset(pack.K_QUANTUM)


def _names(path) -> list[str]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
    return out


def _is_row_parallel(names: list[str]) -> bool:
    return any(n in _ROW_PARALLEL for n in names)


def param_spec(path, leaf, *, fsdp: bool = True, scanned_ok: bool = True) -> P:
    """PartitionSpec for one parameter leaf."""
    names = _names(path)
    shape = leaf.shape
    ndim = len(shape)
    name = names[-1] if names else ""
    fs = "data" if fsdp else None

    # scanned 'mid' stacks carry a leading period axis -> spec gets None front
    lead: tuple = ()
    if "mid" in names and scanned_ok:
        lead, shape, ndim = (None,), shape[1:], ndim - 1

    def out(*dims):
        return P(*lead, *dims)

    if "embed" in names:                       # (V, D): vocab over model only
        # (sharding D over data caused involuntary full-remat gathers in SPMD)
        return out("model", None)
    if name == "rec":                          # sLSTM (H, dh, 4dh): heads
        return out("model", None, None)
    if name in ("scale", "bias", "lam"):       # norms / Lambda: replicate
        return out(*([None] * ndim))
    if name == "w_gates":                      # (Dr, 2)
        return out(fs, None)
    if names and "conv" in names:              # depthwise conv (width, D)/(D,)
        return out(*( [None] * (ndim - 1) + [ "model" ] )) if ndim else P()

    row = _is_row_parallel(names)
    is_expert = ("ffn" in names and ndim == 3 and name in
                 ("w", "w_q") or (name in _PACKED and ndim == 3) or
                 (name == "w_scale" and ndim == 2) or (name == "b" and ndim == 2))

    if name == "w_planes":                     # ((E,) bits, out, K/32) stack
        # NOT the generic packed rule: the leading plane axis makes the
        # non-expert leaf 3D, which the `is_expert` heuristic below would
        # misread as an expert stack. Planes replicate (they are facets of
        # ONE logical weight); out/K shard exactly like the 2D packed leaves.
        if ndim == 4:                          # expert stack (E, b, out, K/32)
            return out("model", None, None, fs) if not row \
                else out("model", None, fs, None)
        return out(None, fs, "model") if row else out(None, "model", fs)
    if name == "w" or name == "w_q":           # dense (in, out) train/int8
        if is_expert:                          # (E, in, out): EP + FSDP
            return out("model", fs, None) if not row else out("model", None, fs)
        if "router" in names:
            return out(fs, None)               # (D, E): tiny, replicate E
        return out("model", fs) if row else out(fs, "model")
    if name in _PACKED:                        # (out, K/32) packed planes
        if is_expert:
            return out("model", None, fs) if not row else out("model", fs, None)
        return out(fs, "model") if row else out("model", fs)
    if name == "w_scale":                      # (out,)
        if is_expert:
            return out("model", None)
        return out(None) if row else out("model")
    if name == "b":                            # bias (out,)
        if is_expert:
            return out("model", None)
        return out(None) if row else out("model")
    if name == "a_scale":
        return P()
    # anything else small: replicate
    return out(*([None] * ndim))


def cache_spec(path, leaf, *, batch_shardable: bool) -> P:
    """PartitionSpec for a KV-cache / recurrent-state leaf."""
    names = _names(path)
    name = names[-1] if names else ""
    shape = leaf.shape
    lead: tuple = ()
    if "mid" in names:
        lead, shape = (None,), shape[1:]
    bdim = ("data",) if batch_shardable else None  # pod handled by caller remap

    def out(*dims):
        return P(*lead, *dims)

    if name in ("k", "v", "cross_k", "cross_v"):   # (B, S, Hk, dh)
        # cache sequence sharded over model (kv-head counts — 8/4/1 — don't
        # divide a 16-way axis; decode attention psums over the seq shards)
        return out(bdim, "model", None, None)
    if name == "C":                                 # (B, H, dk, dv)
        return out(bdim, "model", None, None)
    if name in ("n",):                              # (B, H, dk) or (B, D)
        return out(bdim, "model", None) if len(shape) == 3 else out(bdim, "model")
    if name == "m":                                 # (B, H) or (B, D)
        return out(bdim, *( [None] * (len(shape) - 1) ))
    if name in ("c", "h"):                          # (B, D)
        return out(bdim, "model")
    if name == "conv":                              # (B, w-1, D)
        return out(bdim, None, "model")
    return out(bdim, *([None] * (len(shape) - 1)))


def _widen_dp(spec: P, mesh: Mesh) -> P:
    """Replace 'data' with ('pod','data') on multi-pod meshes for batch dims
    of *data* tensors (params stay un-sharded over pod)."""
    if "pod" not in mesh.axis_names:
        return spec
    return P(*[("pod", "data") if d == ("data",) or d == "data" else d
               for d in spec])


def fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on any dim the mesh axes don't divide exactly (explicit
    pjit in_shardings require divisibility, unlike GSPMD-internal padding)."""
    dims = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for size, d in zip(shape, dims):
        if d is None:
            out.append(None)
            continue
        axes = d if isinstance(d, tuple) else (d,)
        n = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(d if size % n == 0 else None)
    return P(*out)


def _guard_packed_k(spec: P, path, leaf, mesh) -> P:
    """Packed-weight guard: the serve rules shard the *packed* last axis of
    `w_packed`/`w_mask`/`w_sign` (K/32-bit words). A shard boundary must
    never fall inside a packed word, so the axis is only shardable when each
    shard keeps a whole number of words — i.e. the unpacked K divides
    pack_factor(32) x shard_count; a non-dividing packed K falls back to
    replicated instead of a mid-word split.

    Today `fit_spec`'s generic element-count check happens to drop the same
    axes (the packed dim IS counted in words), so this guard exists for two
    other reasons: it names the whole-word invariant explicitly, and it
    routes through `core.pack.shardable_words` — the exact predicate
    `kernels.dispatch.tp_plan` uses — so if `fit_spec` is ever relaxed
    (e.g. to allow GSPMD's padded uneven sharding), packed leaves still
    refuse mid-word splits and the device layout can never disagree with
    the shard_map compute."""
    names = _names(path)
    if not names or names[-1] not in _PACKED:
        return spec
    dims = list(spec) + [None] * (leaf.ndim - len(spec))
    d = dims[-1]
    if d is None:
        return spec
    axes = d if isinstance(d, tuple) else (d,)
    n = int(np.prod([mesh.shape[a] for a in axes]))
    if not pack.shardable_words(leaf.shape[-1], n):
        dims[-1] = None
    return P(*dims)


def param_shardings(mesh: Mesh, param_tree, *, fsdp: bool = True):
    """NamedSharding tree for parameters (train or serve layout)."""
    def one(path, leaf):
        spec = _guard_packed_k(param_spec(path, leaf, fsdp=fsdp),
                               path, leaf, mesh)
        spec = fit_spec(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, param_tree)


def opt_state_shardings(mesh: Mesh, opt_state, param_shardings_tree):
    """Optimizer state shards exactly like its parameter. The int8 moment
    codec is shape-preserving (codes: param shape; scales: param rank with
    last dim = n_blocks), so the param's PartitionSpec applies verbatim —
    the optimizer update is fully local, no resharding collectives."""
    from repro.optim.adamw import AdamWState, Q8Tensor

    def shard_like(ps, mleaf):
        if isinstance(mleaf, Q8Tensor):
            return Q8Tensor(codes=ps, scale=NamedSharding(
                mesh, fit_spec(ps.spec, mleaf.scale.shape, mesh)))
        return ps

    mk = lambda tree: jax.tree.map(
        shard_like, param_shardings_tree, tree,
        is_leaf=lambda x: isinstance(x, NamedSharding))
    return AdamWState(NamedSharding(mesh, P()),
                      mk(opt_state.m), mk(opt_state.v))


def batch_shardings(mesh: Mesh, batch_tree, *, global_batch: int):
    """Inputs: shard batch dim over all dp axes that divide it."""
    dp = [a for a in dp_axes(mesh)]
    size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    axes = tuple(dp) if global_batch % max(size, 1) == 0 else ("data",) \
        if global_batch % mesh.shape.get("data", 1) == 0 else ()

    def one(path, leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        spec = P(axes if axes else None, *([None] * (leaf.ndim - 1)))
        return NamedSharding(mesh, fit_spec(spec, leaf.shape, mesh))
    return jax.tree_util.tree_map_with_path(one, batch_tree)


def serve_cache_shardings(mesh: Mesh, cache_tree):
    """NamedSharding tree for the continuous-batching server's cache.

    Every leaf shards its leading content axis over "data": for paged pool
    leaves (num_pages, page_size, Hk, dh) that is the *page* axis — whole
    pages per shard, so a page's tokens stay device-local and the decode
    gather/scatter through the page table is exact — and for slab leaves
    (window rings, recurrent state, cross-KV) it is the *slot* axis — whole
    requests per shard. The host PageTable (admission, free list) stays
    global numpy; scanned mid-stack leaves carry a leading (n_periods,) dim
    that stays unsharded. Axes the mesh does not divide fall back to
    replicated (fit_spec), e.g. the default pool of slots*max_pages+1 pages
    (the +1 scratch page makes it odd).

    Scheduler state is deliberately OUTSIDE these rules: page refcounts, the
    prefix-share hash index, the free list and preemption swap slabs are all
    host-side numpy (see launch/kv_cache.py) — spilled capacity and
    allocator metadata, not working set, so they never occupy device memory
    or enter a jitted signature. Prefix sharing and copy-on-write only remap
    *which* page ids appear in the (host) table; the device placement rules
    above are unchanged by them — re-verified token-exact under `--mesh` by
    tests/test_serving_sched.py. The same holds for the tiered prefix cache
    (launch/cache_tiers.py) and multi-tenant SlotView windows
    (launch/multi_serve.py): parked pages, host/disk slabs and per-tenant
    slot ranges are all host bookkeeping over the one shared pool, so they
    inherit these rules unmodified.
    """
    def one(path, leaf):
        names = _names(path)
        lead = 1 if "mid" in names else 0
        dims = [None] * leaf.ndim
        if leaf.ndim > lead and "data" in mesh.axis_names:
            dims[lead] = "data"        # page axis (pool) or slot axis (slab)
        return NamedSharding(mesh, fit_spec(P(*dims), leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def repin_serve_cache(mesh: Mesh, cache_tree):
    """Re-apply the serve cache placement after a host-driven update.

    Swap-in scatters a preempted request's host slab back into the pool with
    eager `.at[ids].set` ops, and tier promotion (launch/cache_tiers.py)
    scatters a host/disk slab image the same way; outside jit, sharding
    propagation through such an update is backend-dependent, so the server
    re-pins the result to the canonical `serve_cache_shardings` layout (a
    no-op device_put when the placement already matches). Keeping this here
    — next to the rules it re-applies — means serve.py cannot drift from the
    layout contract."""
    return jax.device_put(cache_tree, serve_cache_shardings(mesh, cache_tree))


def cache_shardings(mesh: Mesh, cache_tree, *, batch: int):
    dp = list(dp_axes(mesh))
    size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    shardable = batch % max(size, 1) == 0

    def one(path, leaf):
        spec = cache_spec(path, leaf, batch_shardable=shardable)
        # widen batch to include pod axis
        dims = list(spec)
        if shardable and dims and dims[0] == ("data",):
            dims[0] = tuple(dp)
        elif dims and isinstance(dims[0], tuple) and "mid" not in _names(path):
            pass
        if "mid" in _names(path) and shardable and len(dims) > 1 and dims[1] == ("data",):
            dims[1] = tuple(dp)
        # singleton axis tuples are NOT equal to the bare name in PartitionSpec
        dims = [d[0] if isinstance(d, tuple) and len(d) == 1 else d for d in dims]
        return NamedSharding(mesh, fit_spec(P(*dims), leaf.shape, mesh))
    return jax.tree_util.tree_map_with_path(one, cache_tree)
