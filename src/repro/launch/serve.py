"""Batched serving driver: continuous batching over the packed (bit-plane)
serve parameters, with a paged KV cache, prefix sharing, and a
preemption + swap scheduler.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --reduced \
        --requests 16 --max-new 32 --paged --prefix-share --preempt

Design (vLLM-style, shrunk to its essentials):
  * fixed `slots` decode batch; a request FIFO feeds free slots
  * admission is metered by the free-page budget (paged mode), not just by
    free slots. Default (conservative) policy: a request waits until the pool
    can cover its whole lifetime plus running requests' reserved headroom, so
    mid-flight page allocation can never fail. With `--preempt`, admission
    only needs the *prompt's* pages — when the pool runs dry mid-decode, the
    lowest-priority running request is preempted: its pages are swapped to a
    host-side numpy slab and freed, and it resumes later (swap-in to fresh
    pages), token-exactly
  * `--prefix-share`: full (and final-partial) prompt pages are keyed by a
    rolling content hash (kv_cache.prefix_keys); admission maps share-index
    hits instead of allocating, so identical prompt prefixes occupy one set
    of physical pages. A shared page is copy-on-write: the scheduler forks it
    (fresh page + device byte copy) before a slot's decode write would land
    inside it
  * prefill runs per admitted request, right-padded to one of a few bucket
    lengths (the jit cache holds <= len(buckets) prefill signatures instead
    of one per prompt length); its KV is scattered into the slot's pages
    (paged; shared pages are skipped — they already hold this prefix) or
    slab row (contiguous)
  * `--chunk-tokens C` folds prefill INTO the decode tick: an admitted
    request holds all its prompt pages up front but sits in a PREFILLING
    state while one C-token chunk of its prompt runs per tick next to the
    fused decode step (token budget per tick = active decode slots + C), so
    a long prompt no longer freezes every in-flight decode slot. Chunked
    prefill writes byte-identical KV to the whole-prompt path (the chunk
    attention mirrors the blockless prefill algebra exactly —
    models/attention.attn_prefill_chunk) and samples the identical first
    token from the final chunk's logits, so every token-exactness oracle
    holds with chunking on. One fixed chunk signature replaces the prefill
    buckets in the jit budget. Falls back to whole-prompt prefill for archs
    that can't represent a partial prefix in pages (recurrent/window state:
    `exact_prefill`) and for the int8 KV cache (chunk-boundary requant is
    not byte-identical)
  * `--spec-draft planes:P --spec-k K` self-speculative decoding: a DRAFT
    pass over the SAME packed weights — int4/int8 layers contract to their
    P leading bit-planes (kernels.dispatch plane-composed cells) — proposes
    K-1 tokens per tick; one full-precision multi-token VERIFY step (the
    chunk-attention algebra) checks them and the longest exactly-matching
    prefix plus one corrected token land at once. Acceptance is exact token
    match, so serving stays token-exact vs the sequential oracle
  * one fused decode step advances every active slot each tick with a
    per-slot position vector — each slot's RoPE phase, cache-write index and
    validity mask follow its own clock, so mixed-length traffic decodes
    correctly (the old aligned-position decode used max(pos) for everyone)
  * dispatch-ahead double buffering (`dispatch_ahead`, default on): while
    step N's decode/chunk execute on device, the host already runs step
    N+1's scheduling (admission, retire prediction, CoW forks, page
    extends, the masked page table and chunk operands) and stores it as a
    *prepared plan*. Correctness fence: every scheduler mutation bumps an
    epoch counter; a plan is consumed only if its snapshot epoch still
    matches (EOS/retire at fix-up, a new submit, or any fork/swap after the
    plan was built fences it, and the tick rebuilds synchronously —
    stats["fences"] vs stats["plan_hits"])
  * EOS retirement: a request with `eos` set retires the step that token is
    sampled — the slot's pages free immediately and later steps neither
    sample nor write KV for it (data/tokenizer.ByteTokenizer supplies real
    EOS ids)
  * retirement frees the slot's pages back to the pool (refcounted: shared
    pages survive for their co-owners); slot reuse, page churn, CoW forks and
    swaps never re-jit (decode, chunk and fork signatures are fixed)
  * packed weights: `pack_for_serve` (binary/ternary bit-planes, int8 codes)

Request lifecycle states: WAITING (queued) -> [PREFILLING (chunked prompt
in flight) ->] RUNNING (slot + pages) -> PREEMPTED (host swap slab, no
pages) -> RUNNING -> done. A PREFILLING slot is never a preemption victim
(no slot is simultaneously PREFILLING and PREEMPTED — partial-chunk swap
images don't exist). Priority is `(priority desc, rid asc)` — FCFS within a
priority class; the scheduler never preempts a victim at-or-above the
claimant's priority, so the oldest running request always finishes (no
livelock).

Sampling: each request carries (temperature, seed); tokens are drawn
host-side by `models.common.sample_token`, a *stateless* rng keyed by
(seed, token index) — replay is deterministic regardless of batching,
preemption, or sharing history, which is what lets the scheduler tests
demand token-exactness. temperature=0 (default) is greedy argmax.

`--contiguous` keeps the old per-slot slab layout as a reference path; both
run the same per-slot-position decode step. See docs/SERVING.md.

`--mesh DATA,MODEL` serves tensor-parallel: qgemm runs under shard_map
(column-parallel qkv/up, row-parallel out/down with a pre-requant int32
psum), packed weights and the paged pool are device-placed by
launch/sharding.py, and the result is token-exact vs. single-device serving
(tests/test_serving_tp.py, tests/test_serving_sched.py). Admission, the
PageTable (refcounts, hash index) and swap slabs stay host-side. When
`slots` does not divide the data axis, the device batch is padded with
inert phys slots (NULL page rows, position 0, token 0) so every lowered
signature divides the axis — the CPU SPMD partitioner miscompiled
non-dividing batches silently (wrong tokens at slots=3/data=2; regression
in tests/test_serving_tp.py).

On a pod this wraps the decode_32k/long_500k dry-run cells: same
decode_step, mesh sharding from launch/sharding.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import kv_cache
from repro.launch import sharding as shardlib
from repro.launch.kv_cache import NULL_PAGE, PageTable, pages_for
from repro.launch.mesh import make_mesh
from repro.models import transformer
from repro.models.common import ModelCtx, sample_token

WAITING, PREFILLING, RUNNING, PREEMPTED = (
    "WAITING", "PREFILLING", "RUNNING", "PREEMPTED")


def _span(name: str, **stats):
    """A host span "serve.<name>" with integer or string `stats`, in the
    profiler's trace on the device's clock (docs/SERVING.md, "Tracing a
    live server"). While no profiler is tracing it records nothing and
    formats none of `stats`."""
    return jax.profiler.TraceAnnotation("serve." + name, **stats)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    temperature: float = 0.0   # 0 => greedy argmax
    seed: int = 0              # stateless sampling stream (with token index)
    priority: int = 0          # larger = more important; FCFS within a class
    eos: int | None = None     # stop token: retire the step it is sampled
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    state: str = WAITING


@dataclasses.dataclass
class _SwapState:
    """Host-side image of a preempted request: its decode position and the
    numpy slab holding its page bytes + per-slot slab rows."""
    pos: int
    data: object


@dataclasses.dataclass
class _Plan:
    """One prepared device tick: which slots decode, the masked page table
    they see, and (chunked mode) the prefill chunk riding along. Built by
    `_build_plan` — either synchronously at the top of a tick, or ahead of
    time while the previous tick is still executing (dispatch-ahead).
    `epoch` snapshots the scheduler-mutation counter at build completion; a
    plan is only consumable while the snapshot still matches (the fence).
    Token values and the position vector are NOT stored: they are filled at
    dispatch from req.out[-1]/slot_pos, which the fence guarantees are the
    values the plan was built for."""
    epoch: int
    active: list                    # decode slot ids (state RUNNING)
    reqs: list                      # Request per active slot (fix-up targets)
    table: np.ndarray | None        # masked (phys_slots, max_pages), paged only
    chunk: dict | None              # chunk operands, see _plan_chunk
    will_retire: tuple = ()         # predicted retires excluded from `active`


#: JAX's persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset:
#: a fixed path in the checkout (the path is part of the cache key, so a
#: directory that moves never hits). Listed in .gitignore.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one directory and return
    it: JAX_COMPILATION_CACHE_DIR when set (JAX reads it itself, so nothing
    is set here), else `COMPILE_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def serve_params(cfg, *, seed: int = 0, mesh=None, plane_twins: bool = False):
    """Random weights from `seed` in the packed serve layout, made where
    they will live: one jit draws and packs them
    (`transformer.init_packed`, one scanned period at a time), so the
    training layout never exists whole. Under `mesh` the program's
    out_shardings are the serve sharding rules and each training-layout
    piece is pinned to the same rules before packing: no device ever holds
    the unsharded model.

    Returns (packed params, bytes of the training layout)."""
    key = jax.random.PRNGKey(seed)
    constrain = lambda tree: tree
    if mesh is not None:
        constrain = lambda tree: jax.lax.with_sharding_constraint(
            tree, shardlib.param_shardings(mesh, tree, fsdp=False))

    def build(k):
        return transformer.init_packed(k, cfg, plane_twins=plane_twins,
                                       constrain=constrain)

    out_shardings = None
    if mesh is not None:
        out_shardings = shardlib.param_shardings(
            mesh, jax.eval_shape(build, key), fsdp=False)
    sparams = jax.jit(build, out_shardings=out_shardings)(key)
    train_shapes = jax.eval_shape(lambda k: transformer.init(k, cfg), key)
    train_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(train_shapes))
    return sparams, train_bytes


def default_buckets(lo: int, hi: int) -> tuple[int, ...]:
    """Powers-of-two prefill buckets in [lo, hi], always ending at hi."""
    out, b = [], max(lo, 1)
    while b < hi:
        out.append(b)
        b *= 2
    return tuple(out) + (hi,)


class Server:
    def __init__(self, cfg, params, *, slots: int = 4, cache_len: int = 256,
                 paged: bool = True, page_size: int = 32,
                 num_pages: int | None = None,
                 buckets: tuple[int, ...] | None = None,
                 prefix_share: bool = False, preempt: bool = False,
                 chunk_tokens: int = 0, dispatch_ahead: bool = True,
                 spec_draft: str | None = None, spec_k: int = 4,
                 moe_ep: bool = True,
                 ctx: ModelCtx | None = None, mesh=None,
                 page_table=None, model_id: str | None = None,
                 tier=None, tier_watermark: int = 0):
        self.cfg = cfg
        self.sp = transformer.build_specs(cfg)
        self.params = params
        self.ctx = ctx or ModelCtx(mode="serve")
        self.mesh = mesh
        data_dim = 1
        if mesh is not None:
            # tensor-parallel serving: qgemm runs under shard_map on the
            # "model" axis (column/row per layer spec), batch/pages shard
            # over "data". Admission and the PageTable stay host-global.
            from repro.kernels.dispatch import TPSpec
            self.ctx = dataclasses.replace(
                self.ctx, tp=TPSpec(mesh=mesh, axis="model"))
            data_dim = int(mesh.shape["data"])
            if cfg.n_experts and moe_ep:
                # expert-parallel MoE: expert stacks are E-sharded over
                # "model" (the serve param layout already places them there)
                # and the grouped dispatch runs each shard's local experts
                # only — see kernels/dispatch.py EP section. ep_plan falls
                # back per layer when E % model_dim != 0, matching the
                # sharding rules' fit_spec drop.
                from repro.kernels.dispatch import EPSpec
                self.ctx = dataclasses.replace(
                    self.ctx, ep=EPSpec(mesh=mesh, axis="model"))
        if cfg.n_experts:
            # routing telemetry: the serve entry points return a third
            # {"expert_tokens", "dropped", "rows"} value; _pop_moe queues it
            # and _drain_moe folds it into Server.stats AFTER the tick's fix-up
            # sync (converting at dispatch would sync the stream and kill
            # the dispatch-ahead overlap)
            self.ctx = dataclasses.replace(self.ctx, moe_stats=True)
        self.slots = slots
        # the CPU SPMD partitioner silently miscompiles batched serve steps
        # whose slot dim does not divide the data axis (wrong tokens, not an
        # error — seed-reproducible at slots=3/data=2). Pad the device batch
        # to the next multiple with inert phys slots: NULL page rows,
        # position 0, token 0 — their writes land on scratch page 0 and the
        # scheduler never looks at them. Host-side scheduling stays at
        # `slots`; only device shapes use `phys_slots`.
        self.phys_slots = -(-slots // data_dim) * data_dim
        self.paged = paged
        self.page_size = page_size
        self.prefix_share = bool(prefix_share)
        self.preempt = bool(preempt)
        if (self.prefix_share or self.preempt) and not paged:
            raise ValueError("--prefix-share/--preempt need the paged cache "
                             "(--contiguous keeps the conservative slab path)")
        if chunk_tokens and not paged:
            raise ValueError("--chunk-tokens needs the paged cache (a partial "
                             "prefix is only representable through pages)")
        if paged and cache_len % page_size:
            cache_len += page_size - cache_len % page_size
        self.cache_len = cache_len
        # right-padded prefill is only safe for pure full attention: padding
        # KV would pollute recurrent state outright, and a sliding-window
        # ring keeps the last `window` tokens of the PADDED sequence (the
        # ring-full mask then attends the padding). Those archs bucket to
        # the exact prompt length instead.
        self.exact_prefill = any(k != "attn" for k in cfg.block_pattern)
        # chunked prefill needs (a) a paged partial prefix — so no recurrent
        # /window state — and (b) pool dtype == compute dtype, or the chunk
        # boundary requant breaks KV byte-identity vs whole-prompt prefill.
        # Fall back to whole-prompt bucketed prefill otherwise.
        self.chunk_tokens = int(chunk_tokens or 0)
        if self.chunk_tokens and (self.exact_prefill
                                  or cfg.kv_cache_dtype == "int8"):
            self.chunk_tokens = 0
        self.dispatch_ahead = bool(dispatch_ahead)
        # self-speculative decoding: a truncated-bit-plane DRAFT pass over
        # the SAME packed weights and pages proposes spec_k-1 tokens per
        # tick; one full-precision multi-token VERIFY step (the chunk
        # attention algebra) checks them, and the accepted prefix plus the
        # first corrected token land at once. Token-exact vs sequential
        # decoding — acceptance is exact token match against what the
        # full-precision pass samples, never a distribution test.
        self.spec = bool(spec_draft)
        self.spec_k = int(spec_k)
        self.spec_planes = 1
        if self.spec:
            kind, _, depth = spec_draft.partition(":")
            if kind != "planes":
                raise ValueError(f"unknown --spec-draft kind {kind!r} "
                                 "(only 'planes[:DEPTH]' exists)")
            self.spec_planes = int(depth) if depth else 1
            if self.spec_k < 1:
                raise ValueError("--spec-k must be >= 1")
            if not paged:
                raise ValueError("--spec-draft needs the paged cache (the "
                                 "verify step replays a multi-token range "
                                 "through pages)")
            if self.chunk_tokens:
                raise ValueError("--spec-draft and --chunk-tokens are "
                                 "mutually exclusive")
            if self.exact_prefill or cfg.kv_cache_dtype == "int8":
                # verify rides the chunk attention path: recurrent/window
                # state can't replay a token range, and the int8 KV requant
                # is not byte-identical at chunk boundaries — fall back to
                # plain sequential decoding rather than lose exactness
                self.spec = False
            else:
                self.dispatch_ahead = False   # spec ticks schedule in line
        if self.spec:
            # layers packed in a direct int4/int8 layout need the plane twin
            # for the draft pass to read (policies with no such layers fall
            # back to a full-precision draft via operating_point's impl
            # fallback — trivially exact, accept-rate 1)
            leaves = {getattr(p[-1], "key", None) for p, _ in
                      jax.tree_util.tree_leaves_with_path(params)}
            if {"w_q", "w_q4"} & leaves and "w_planes" not in leaves:
                raise ValueError(
                    "--spec-draft needs the bit-plane weight twin; pack "
                    "with transformer.pack_for_serve(..., plane_twins=True)")
        if buckets is None:
            buckets = default_buckets(page_size if paged else 8, cache_len)
        self.buckets = tuple(sorted(buckets))

        # pool dtype must match what prefill/decode actually store: the
        # compute dtype, unless the int8-requant cache is configured —
        # otherwise every scatter silently rounds the prefill KV
        kv_dtype = None if cfg.kv_cache_dtype == "int8" else self.ctx.dtype
        # multi-tenant namespace: mixed into every prefix key (hash root +
        # verbatim bytes) so co-tenant models can never alias a page, and
        # the tag under which this server's tier demoter registers
        self.model_id = model_id
        self.ns = model_id.encode() if model_id else b""
        # full-coverage prefill skip (tiered / shared re-admission): when
        # every prompt page arrives from the share index, the first-token
        # logits come from a single 1-token chunk step over the resident KV
        # instead of a full re-prefill. Same algebra constraints as chunked
        # prefill: no recurrent/window state, no int8 KV requant.
        self._skip_prefill_ok = (paged and not self.exact_prefill
                                 and cfg.kv_cache_dtype != "int8")
        if paged:
            self.max_pages = cache_len // page_size
            if page_table is not None:
                # multi-tenant: a SlotView window onto the shared pool
                if page_table.slots != self.phys_slots:
                    raise ValueError(
                        f"page_table view has {page_table.slots} slots, "
                        f"server needs {self.phys_slots}")
                self.pt = page_table
                num_pages = page_table.num_pages
            else:
                if num_pages is None:
                    num_pages = slots * self.max_pages + 1  # +1: scratch page 0
                if tier is not None:
                    from repro.launch.cache_tiers import TieredPageTable
                    self.pt = TieredPageTable(
                        num_pages, page_size, self.phys_slots, self.max_pages,
                        store=tier, watermark=tier_watermark)
                    self.pt._current_ns = self.ns
                else:
                    self.pt = PageTable(num_pages, page_size, self.phys_slots,
                                        self.max_pages)
            self.cache = transformer.init_cache(cfg, self.phys_slots, cache_len,
                                                paged=(num_pages, page_size),
                                                kv_dtype=kv_dtype)
            self.paged_mask = kv_cache.paged_leaf_mask(
                cfg, self.phys_slots, cache_len, num_pages, page_size)
            if hasattr(self.pt, "register_demoter"):
                self.pt.register_demoter(
                    self.ns,
                    lambda pid: kv_cache.gather_pages(self.cache, [pid],
                                                      self.paged_mask))
        else:
            if page_table is not None or tier is not None:
                raise ValueError("page_table/tier need the paged cache")
            self.pt = None
            self.cache = transformer.init_cache(cfg, self.phys_slots, cache_len,
                                                kv_dtype=kv_dtype)
            self.paged_mask = None

        if mesh is not None:
            # place packed weights by the serve sharding rules (column: N
            # over "model"; row: packed-K words over "model" — guarded by
            # pack.shardable_words) and the cache per-data-shard (pool pages
            # / slab slots over "data"); non-dividing axes replicate. The
            # shard_map in qgemm then consumes the shards in place.
            self.params = jax.device_put(
                self.params,
                shardlib.param_shardings(mesh, self.params, fsdp=False))
            self.cache = jax.device_put(
                self.cache, shardlib.serve_cache_shardings(mesh, self.cache))

        self.slot_req: list[Request | None] = [None] * slots
        self.slot_pos = np.zeros(self.phys_slots, np.int32)
        self.queue: list[Request] = []
        self.preempted: list[Request] = []
        self._swap: dict[int, _SwapState] = {}
        self._prefill_ctx: dict[int, dict] = {}   # slot -> chunked-prefill state
        self.completed: list[Request] = []
        self.pos_trace: list[np.ndarray] = []   # per-tick active-slot positions
        self.stats = {"shared_pages": 0, "cow_forks": 0,
                      "preemptions": 0, "resumes": 0, "peak_pages": 0,
                      "chunk_ticks": 0, "plan_hits": 0, "fences": 0,
                      "spec_ticks": 0, "spec_proposed": 0,
                      "spec_accepted": 0, "spec_emitted": 0,
                      "admitted": 0, "prefill_skips": 0,
                      "tier_hits_device": 0, "tier_hits_host": 0,
                      "tier_hits_disk": 0, "decode_inplace_layers": 0}
        if cfg.n_experts:
            # moe_routed = total top-k assignments (kept + dropped);
            # moe_expert_tokens[e] = assignments expert e actually served;
            # moe_expert_rows = rows the expert GEMMs computed (E x B*C per
            # layer and call, capacity padding included)
            self.stats.update({"moe_routed": 0, "moe_dropped": 0,
                               "moe_expert_rows": 0,
                               "moe_expert_tokens": [0] * cfg.n_experts})
        self._moe_pending: list = []
        # multi-tenant hooks (set by launch/multi_serve.MultiServer):
        # extern_demand() -> pages co-tenant running slots may still claim
        # (joins this server's conservative admission reservation);
        # reclaim_hook(worse_than) -> True if it preempted one strictly-
        # lower-priority co-tenant slot (extends _make_room across tenants)
        self.extern_demand = None
        self.reclaim_hook = None
        # dispatch-ahead state: the prepared next tick and the mutation epoch
        # that fences it (every scheduler mutation — admit, retire, preempt,
        # resume, fork, submit — bumps the epoch; a plan built at epoch e is
        # dead the moment the epoch moves past e)
        self._epoch = 0
        self._prepared: _Plan | None = None

        self.compile_counts = {"prefill": 0, "decode": 0, "cow": 0,
                               "chunk": 0, "draft": 0, "verify": 0}
        self._signatures: dict[str, set] = {k: set()
                                            for k in self.compile_counts}
        self._prefill = self._counted("prefill", lambda p, t, lp:
            transformer.prefill(p, t, self.sp, self.ctx,
                                cache_len=self.cache_len, last_pos=lp))
        if paged:
            self._decode = self._counted("decode", lambda p, c, t, pos, pg:
                self._decode_step(p, c, t, pos, self.ctx, pages=pg),
                donate_cache=True)
            # CoW byte copy: scalar (src, dst) page ids -> fixed signature,
            # so fork traffic compiles exactly once
            self._cow = self._counted("cow", lambda c, a, b:
                kv_cache.copy_page(c, a, b, self.paged_mask))
            self._chunk = self._counted("chunk", lambda p, c, t, p0, rp, wp, nr, li:
                transformer.prefill_chunk(p, c, t, p0, self.sp, self.ctx,
                                          read_pages=rp, write_pages=wp,
                                          nreal=nr, last_idx=li))
            if self.spec:
                # draft context: layers that resolve to a plane-composed
                # cell contract to the leading spec_planes MSB planes (the
                # sign plane alone at depth 1); everything else — and every
                # policy pair without a plane cell — runs full precision,
                # so the draft degrades toward exact instead of breaking
                draft_ctx = dataclasses.replace(self.ctx, impl="planes",
                                                draft_planes=self.spec_planes)
                self._draft = self._counted("draft", lambda p, c, t, pos, pg:
                    self._decode_step(p, c, t, pos, draft_ctx, pages=pg))
                self._verify = self._counted("verify",
                    lambda p, c, t, p0, rp, wp, nr:
                        transformer.decode_verify(p, c, t, p0, self.sp,
                                                  self.ctx, read_pages=rp,
                                                  write_pages=wp, nreal=nr))
        else:
            self._decode = self._counted("decode", lambda p, c, t, pos:
                self._decode_step(p, c, t, pos, self.ctx), donate_cache=True)

    def _decode_step(self, params, cache, tokens, pos, ctx, pages=None):
        """`transformer.decode_step`, keeping in stats["decode_inplace_layers"]
        how many layers of the traced program update the paged pool in
        place (every full-attention layer of a paged server)."""
        seen = {}
        out = transformer.decode_step(params, cache, tokens, pos, self.sp,
                                      ctx, pages=pages, trace_counts=seen)
        self.stats["decode_inplace_layers"] = seen["inplace_layers"]
        return out

    @staticmethod
    def _abstract_sig(args):
        """Abstract signature of a traced call: treedef + per-leaf
        (shape, dtype, weak_type) — exactly what decides whether jax.jit
        re-traces, minus sharding/donation (which the server holds fixed)."""
        leaves, treedef = jax.tree.flatten(args)
        def leaf_sig(l):
            a = getattr(l, "aval", None)
            if a is not None:
                return (tuple(a.shape), str(a.dtype),
                        bool(getattr(a, "weak_type", False)))
            return ("static", repr(l))
        return (treedef, tuple(leaf_sig(l) for l in leaves))

    def _counted(self, key: str, fn, donate_cache: bool = False):
        """jit(fn) with signature-set accounting: compile_counts[key] is the
        number of DISTINCT abstract signatures ever traced under `key` — not
        a call-site trace tally. A re-trace of a signature already seen
        (jit-cache eviction, jax.clear_caches) does not inflate the count,
        and a new signature slipping through a reused key always raises it —
        what the --jit-budget gate actually wants to bound.

        The program is named `serve_<key>`, so the device trace shows its
        runs as the module `jit_serve_<key>`. `donate_cache` donates the
        cache (argument 1): the output cache then aliases the input's
        buffers, which the call invalidates — the caller rebinds
        `self.cache` from the result."""
        def traced(*args):
            self._signatures[key].add(self._abstract_sig(args))
            self.compile_counts[key] = len(self._signatures[key])
            return fn(*args)
        traced.__name__ = traced.__qualname__ = f"serve_{key}"
        return jax.jit(traced, donate_argnums=(1,) if donate_cache else ())

    # -- request lifecycle -----------------------------------------------------

    def _pop_moe(self, res, count: bool = True):
        """Strip the trailing MoE-stats leaf from a jitted serve-step result
        (the ctx.moe_stats 3-tuple contract) and queue the device arrays for
        the deferred drain. `count=False` drops the stats instead (the spec
        DRAFT pass re-routes the same positions the verify step counts —
        counting both would double-book). No-op when stats are off."""
        if not self.ctx.moe_stats:
            return res
        *rest, st = res
        if count and st is not None:
            self._moe_pending.append(st)
        return tuple(rest)

    def _drain_moe(self):
        """Fold queued per-call routing counters into Server.stats. Called at
        the END of a tick, after fix-up already synced the device stream —
        np.asarray here is free, while converting at dispatch would serialize
        dispatch-ahead."""
        if not self._moe_pending:
            return
        with _span("moe_drain", calls=len(self._moe_pending)):
            for st in self._moe_pending:
                et = np.asarray(st["expert_tokens"])
                dropped = int(np.asarray(st["dropped"]))
                self.stats["moe_dropped"] += dropped
                self.stats["moe_routed"] += int(et.sum()) + dropped
                self.stats["moe_expert_rows"] += int(np.asarray(st["rows"]))
                self.stats["moe_expert_tokens"] = [
                    a + int(b)
                    for a, b in zip(self.stats["moe_expert_tokens"], et)]
            self._moe_pending.clear()

    def submit(self, req: Request):
        if len(req.prompt) > self.buckets[-1]:
            raise ValueError(f"prompt len {len(req.prompt)} exceeds max bucket "
                             f"{self.buckets[-1]}")
        if self.paged:
            # lifetime pages alone decide servability: a request that ends up
            # running solo can never need a CoW fork (refcount > 1 requires a
            # live co-owner slot), so no +1 for sharing here — the per-tick
            # fork debt is reserved by admission, not by submit
            need = pages_for(self._need_tokens(req), self.page_size)
            if need > self.pt.usable_pages:
                # un-admittable head would livelock run(): admission (and,
                # under --preempt, a solo run after evicting everyone) waits
                # for pages the pool can never have
                raise ValueError(
                    f"request needs {need} pages but the pool only has "
                    f"{self.pt.usable_pages} usable; raise --num-pages or "
                    f"shrink the request")
        req.state = WAITING
        self.queue.append(req)
        self._epoch += 1   # fence: a prepared plan didn't see this arrival

    def _bucket(self, n: int) -> int:
        if self.exact_prefill:
            return n    # exact-length prefill (recurrent / windowed layers)
        return next(b for b in self.buckets if b >= n)

    def _need_tokens(self, req: Request) -> int:
        """KV tokens this request can write over its whole lifetime."""
        return min(len(req.prompt) + max(req.max_new, 1) - 1, self.cache_len)

    @staticmethod
    def _prio(req: Request):
        """Scheduler key: smaller sorts first = more important. Larger
        `priority` wins; FCFS (rid) breaks ties. Victims are chosen from the
        max end, so the oldest highest-priority request is never preempted."""
        return (-req.priority, req.rid)

    def _sample(self, req: Request, logits_row) -> int:
        return sample_token(logits_row, req.temperature, req.seed,
                            len(req.out))

    # -- admission -------------------------------------------------------------

    def _outstanding_demand(self) -> int:
        """Pages active slots may still claim (their reserved headroom)."""
        return sum(
            pages_for(self._need_tokens(r), self.page_size) - int(self.pt.held[s])
            for s, r in enumerate(self.slot_req) if r is not None)

    def _fork_debt(self, extra_shared=frozenset(),
                   extra_writer_pages=()) -> int:
        """Pages CoW forks may still claim, counted exactly per PHYSICAL
        page: a page with effective refcount r and w slots whose next decode
        write lands inside it can absorb at most min(w, r - 1) forks — each
        fork drops the refcount by one, and the last co-owner standing
        writes in place, no copy. The old per-slot tally (one page per slot
        with a pending CoW, plus one for the candidate's own shared
        boundary page) overcounted aliased writers and double-counted a
        page against both the candidate and the slot it shares with — e.g.
        an in-flight PREFILLING slot whose deferred index registration is
        about to cover that very page — rejecting admissible work under
        --prefix-share + --chunk-tokens.

        `extra_shared`: pages a candidate admission would map (effective
        refcount +1 each). `extra_writer_pages`: pages the candidate itself
        will write into on its first decode (its boundary page when that
        arrives shared). For a PREFILLING slot the next decode write is at
        position n (its chunk clock slot_pos is still inside the prompt)."""
        writers: dict[int, int] = {}
        for s, r in enumerate(self.slot_req):
            if r is None:
                continue
            pos = (self._prefill_ctx[s]["n"] if r.state == PREFILLING
                   else int(self.slot_pos[s]))
            idx = pos // self.page_size
            if idx >= int(self.pt.held[s]):
                continue              # next write opens a fresh page
            pid = int(self.pt.table[s, idx])
            writers[pid] = writers.get(pid, 0) + 1
        for pid in extra_writer_pages:
            writers[pid] = writers.get(pid, 0) + 1
        debt = 0
        for pid, w in writers.items():
            rc = int(self.pt.refcount[pid]) + (1 if pid in extra_shared else 0)
            if rc > 1:
                debt += min(w, rc - 1)
        return debt

    def _admission_ok(self, req: Request, keys) -> bool:
        """Page-budget admission test for the queue head.

        --preempt: only the prompt's pages (minus share hits) must be free —
        decode headroom is reclaimed later by preempting, so the conservative
        reservation no longer rejects admissible work (PageTable.can_admit's
        `reclaimable` is the same accounting, used on the resume path).
        Default: lifetime reservation — free pages must cover this request's
        whole lifetime plus every running request's remaining headroom and
        pending CoW-fork debt, so extend/fork can never fail mid-flight.
        """
        hits = self.pt.lookup_keys(keys) if keys is not None else []
        nhit = sum(1 for p in hits if p is not None)
        # effective supply: a tiered table's parked pages count as free, but
        # parked pages this probe HITS will be mapped, not reclaimed — they
        # must not fund the miss allocations (free_pages_for nets them out)
        free = (self.pt.free_pages_for(keys)
                if hasattr(self.pt, "free_pages_for") else self.pt.free_pages)
        if self.preempt:
            need_now = pages_for(len(req.prompt), self.page_size) - nhit
            return free >= need_now
        lifetime = pages_for(self._need_tokens(req), self.page_size) - nhit
        extern = self.extern_demand() if self.extern_demand is not None else 0
        debt = 0
        if self.prefix_share:
            # the candidate's own first decode write lands in its final
            # prompt page; when that page arrives shared (a boundary hit) it
            # is one more writer in the same per-page accounting — not an
            # unconditional +1 on top (that double-counted it against the
            # slot it shares with)
            boundary = (hits[-1],) if (hits and hits[-1] is not None
                                       and len(req.prompt) % self.page_size
                                       ) else ()
            debt = self._fork_debt({p for p in hits if p is not None},
                                   boundary)
        return free - self._outstanding_demand() - debt - extern >= lifetime

    def _tier_promote(self, keys):
        """Re-materialize host/disk-tier slabs for this prompt's leading
        missing prefix pages, so the admission that follows maps them as
        share hits (and, on full coverage, skips prefill outright).

        Prefix-closed walk: accumulate the verbatim chain over consumed
        keys; at the first share-index miss, probe the store with the
        restart-stable content key `(covered, hash, chain)`. A store hit is
        adopted — allocated, registered under the live `(parent, key)`
        chain, parked at refcount 0 — and its bytes scattered into this
        server's pool before anything can map it. The walk stops at the
        first store miss (deeper pages are unreachable without it) and
        never evicts to fund itself (promotion only spends REAL free pages
        — cannibalizing the device tier to fill the device tier is churn).
        """
        store = getattr(self.pt, "store", None)
        if store is None or not keys:
            return
        hits = self.pt.lookup_keys(keys)
        parent, chain = kv_cache._ROOT, b""
        for key, hit in zip(keys, hits):
            if hit is not None:
                parent, chain = hit, chain + key[2]
                continue
            chain = chain + key[2]
            if not getattr(self.pt, "_free", ()):
                break              # no real free page to land the slab on
            image, tiername = store.get((key[0], key[1], chain))
            if image is None:
                break
            page = self.pt.adopt(parent, key, chain, self.ns)
            self.cache = kv_cache.scatter_pages(self.cache, image, [page],
                                                self.paged_mask)
            if self.mesh is not None:
                self.cache = shardlib.repin_serve_cache(self.mesh, self.cache)
            self.stats["tier_hits_host" if tiername == "host"
                       else "tier_hits_disk"] += 1
            parent = page

    def _count_device_hits(self, keys):
        """Per-tenant device-tier accounting: share hits about to re-admit
        a PARKED page are device-tier hits for this server (the table's own
        counter is pool-global)."""
        if keys is None or not hasattr(self.pt, "is_cached"):
            return
        self.stats["tier_hits_device"] += sum(
            1 for p in self.pt.lookup_keys(keys)
            if p is not None and self.pt.is_cached(p))

    def _skip_prefill(self, s: int, req: Request, n: int):
        """First-token logits for a fully-resident prompt (every page came
        from the share index) via ONE 1-token chunk step at position n-1 —
        no re-prefill. The write table is all-NULL: the resident pages are
        shared/parked and must not be rewritten (their bytes are already
        byte-identical to what this prompt's prefill would produce); the
        chunk's in-flight K/V for its own row feeds the attention directly,
        so the logits match the full prefill's final row bit-for-bit
        (jit-vs-jit, same algebra as the chunked-prefill final chunk)."""
        read = self.pt.table[s].copy()
        write = np.full_like(read, NULL_PAGE)
        toks = np.asarray([[req.prompt[-1]]], np.int32)
        c_logits, self.cache = self._pop_moe(self._chunk(
            self.params, self.cache, jnp.asarray(toks),
            jnp.asarray([n - 1], jnp.int32), jnp.asarray(read)[None],
            jnp.asarray(write)[None], jnp.asarray([1], jnp.int32),
            jnp.asarray([0], jnp.int32)))
        with _span("sync", what="chunk"):
            row = np.asarray(c_logits)[0, 0]
        req.out.append(self._sample(req, row))
        self.stats["prefill_skips"] += 1

    def _try_start(self, s: int) -> bool:
        """Prefill + admit the queue head into slot s (False: it must wait)."""
        req = self.queue[0]
        keys = None
        if self.paged:
            keys = (kv_cache.prefix_keys(req.prompt, self.page_size,
                                         namespace=self.ns)
                    if self.prefix_share else None)
            if keys is not None:
                self._tier_promote(keys)
            if not self._admission_ok(req, keys):
                return False   # FIFO: the head waits for pages; no jumping
        self.queue.pop(0)
        n = len(req.prompt)
        with _span("admit", rid=req.rid, tokens=n, bucket=self._bucket(n)):
            self._prefill_into(s, req, keys)
        return True

    def _prefill_into(self, s: int, req: Request, keys):
        """Prefill the admitted `req` into slot s: map or claim its pages,
        sample its first token, scatter its KV."""
        n = len(req.prompt)
        if self.paged and keys is not None:
            self._count_device_hits(keys)
            ids, shared = self.pt.admit_shared(s, n, keys)
            self.stats["shared_pages"] += int(shared.sum())
            if shared.all() and self._skip_prefill_ok:
                # the whole prompt is already resident — first token from
                # one chunk step over the shared pages, no prefill at all
                self._skip_prefill(s, req, n)
                self._finish_start(s, req, n)
                return
            # shared pages already hold this prefix's KV (and possibly a
            # co-owner's decode bytes past it) — never rescatter them
            scatter_ids = np.where(shared, NULL_PAGE, ids).astype(np.int32)
        elif self.paged:
            scatter_ids = self.pt.admit(s, n)
        bucket = self._bucket(n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = req.prompt
        logits, rc = self._pop_moe(self._prefill(
            self.params, jnp.asarray(toks), jnp.asarray([n - 1], jnp.int32)))
        with _span("sync", what="prefill"):
            row = np.asarray(logits[0, -1])
        req.out.append(self._sample(req, row))
        with _span("scatter", rid=req.rid):
            if self.paged:
                pad = pages_for(bucket, self.page_size) - len(scatter_ids)
                if pad:
                    scatter_ids = np.concatenate(
                        [scatter_ids, np.full(pad, NULL_PAGE, np.int32)])
                self.cache = kv_cache.scatter_prefill(
                    self.cache, rc, s, paged_mask=self.paged_mask,
                    page_ids=scatter_ids, page_size=self.page_size)
            else:
                self.cache = kv_cache.scatter_prefill(self.cache, rc, s)
        self._finish_start(s, req, n)

    def _finish_start(self, s: int, req: Request, n: int):
        req.state = RUNNING
        self.slot_req[s] = req
        self.slot_pos[s] = n
        self.stats["admitted"] += 1
        self._epoch += 1

    def _defer_for_inflight(self, keys) -> bool:
        """True if the queue head must wait one tick: its first prefix page
        misses the share index but an in-flight PREFILLING slot is building
        exactly that page (same first key). Admitting now would allocate a
        private copy of a prefix about to become shareable — deferring keeps
        chunked prefix sharing as effective as the whole-prompt path, where
        admission and indexing were atomic."""
        if not keys:
            return False
        if self.pt.lookup_keys(list(keys[:1]))[0] is not None:
            return False
        for s, r in enumerate(self.slot_req):
            if r is not None and r.state == PREFILLING:
                okeys = self._prefill_ctx[s]["keys"]
                if okeys and okeys[0] == keys[0]:
                    return True
        return False

    def _start_chunked(self, s: int) -> bool:
        """Admit the queue head into slot s in PREFILLING state (chunked
        prefill). All prompt pages are claimed up front — the page-budget
        accounting is identical to `_try_start` — but no prefill runs here:
        step() feeds one --chunk-tokens chunk per tick through the fused
        chunk step. Leading shared pages already hold this prefix's KV, so
        the chunk clock starts past them (always leaving >= 1 token: the
        final chunk must produce the first-token logits). Share-index
        registration of the slot's own pages is deferred until chunks
        actually cover them (PageTable.index_pages at each chunk landing)."""
        req = self.queue[0]
        keys = (kv_cache.prefix_keys(req.prompt, self.page_size,
                                     namespace=self.ns)
                if self.prefix_share else None)
        if keys is not None:
            self._tier_promote(keys)
        if not self._admission_ok(req, keys):
            return False   # FIFO: the head waits for pages; no jumping
        if self._defer_for_inflight(keys):
            return False
        self.queue.pop(0)
        n = len(req.prompt)
        shared = None
        lead = 0
        # bucket 0: no whole-prompt prefill runs, the chunks carry it
        with _span("admit", rid=req.rid, tokens=n, bucket=0):
            if keys is not None:
                self._count_device_hits(keys)
                ids, shared = self.pt.admit_shared(s, n, keys,
                                                   defer_index=True)
                self.stats["shared_pages"] += int(shared.sum())
                if shared.all() and self._skip_prefill_ok:
                    # fully resident (tier re-admission): no chunks to run
                    # at all — sample the first token and go to RUNNING
                    self._skip_prefill(s, req, n)
                    self._finish_start(s, req, n)
                    return True
                while lead < len(shared) and shared[lead]:
                    lead += 1
            else:
                self.pt.admit(s, n)
        self._prefill_ctx[s] = {"keys": keys, "shared": shared, "n": n}
        req.state = PREFILLING
        self.slot_req[s] = req
        self.slot_pos[s] = min(lead * self.page_size, n - 1)  # chunk clock
        self.stats["admitted"] += 1
        self._epoch += 1
        return True

    def _admit(self):
        """Fill free slots: resume preempted requests first (strict priority
        — fresh work never jumps a swapped-out request), then the FIFO head."""
        for s in range(self.slots):
            if self.slot_req[s] is not None:
                continue
            if self.preempted:
                if not self._resume_into(s):
                    break
                continue
            if not self.queue:
                break
            started = (self._start_chunked(s) if self.chunk_tokens
                       else self._try_start(s))
            if not started:
                break

    # -- preemption / swap -----------------------------------------------------

    def _preempt(self, s: int):
        """Swap slot s out: gather its page bytes + slab rows to a host numpy
        slab, release its pages (refcounted — shared pages survive for their
        co-owners), and park the request on the preempted list."""
        req = self.slot_req[s]
        # gather exactly the pages the resume will scatter back: those
        # covering the decode position. Speculative ticks extend coverage
        # past pos (the verify step writes lookahead rows); those pages hold
        # rejected-draft garbage and must not enter the swap image —
        # swap_in_slot scatters pages_for(pos) pages, a larger slab would
        # shape-mismatch. swap_out below still releases EVERY held page.
        with _span("preempt", rid=req.rid):
            ids = self.pt.slot_pages(s)[: pages_for(int(self.slot_pos[s]),
                                                    self.page_size)]
            data = kv_cache.swap_out_slot(self.cache, s, ids, self.paged_mask)
            self.pt.swap_out(s)
        self._swap[req.rid] = _SwapState(int(self.slot_pos[s]), data)
        req.state = PREEMPTED
        self.preempted.append(req)
        self.slot_req[s] = None
        self.slot_pos[s] = 0
        self.stats["preemptions"] += 1
        self._epoch += 1

    def _make_room(self, need_free: int, worse_than) -> bool:
        """Preempt strictly-lower-priority running requests (worst first)
        until `need_free` pages are free. False if victims run out."""
        while self.pt.free_pages < need_free:
            # only RUNNING slots are eligible victims: a PREFILLING slot has
            # no well-defined swap image (its pages are mid-chunk) and no
            # saved decode position to resume from
            victims = [s for s, r in enumerate(self.slot_req)
                       if r is not None and r.state == RUNNING
                       and self._prio(r) > worse_than]
            if not victims:
                # multi-tenant: ask the coordinator to preempt a strictly-
                # lower-priority co-tenant slot (frees pages in the SHARED
                # pool); victims shrink every call, so this terminates
                if (self.reclaim_hook is not None
                        and self.reclaim_hook(worse_than)):
                    continue
                return False
            self._preempt(max(victims,
                              key=lambda v: self._prio(self.slot_req[v])))
        return True

    def _resume_into(self, s: int) -> bool:
        """Swap the most-important preempted request back into slot s."""
        req = min(self.preempted, key=self._prio)
        st = self._swap[req.rid]
        # cover through the NEXT write (pos + 1), not just the saved
        # coverage: resuming into exactly pages_for(pos) free pages would
        # swap the whole KV in only for _prepare_pages to find the pool dry
        # at its extend and swap it straight back out — a full round trip
        # with zero decode progress (swapped-in pages are private, so no
        # CoW page is ever needed on top). swap_in CLAIMS that coverage
        # immediately — a later resume or admission in this same pass cannot
        # consume the write page out from under an earlier, more important
        # resume (a pre-check alone would not be held across the pass).
        cover = min(st.pos + 1, self.max_pages * self.page_size)
        need = pages_for(cover, self.page_size)
        if self.pt.free_pages < need:
            reclaim = sum(int(self.pt.held[v])
                          for v, r in enumerate(self.slot_req)
                          if r is not None and r.state == RUNNING
                          and self._prio(r) > self._prio(req))
            if not self.pt.can_admit(cover, reclaimable=reclaim):
                return False
            # can_admit's reclaimable may overcount shared pages; verify by
            # actually evicting, and give up until next tick if it falls short
            if not self._make_room(need, self._prio(req)):
                return False
        with _span("resume", rid=req.rid):
            ids = self.pt.swap_in(s, cover)
            # the saved slab covers pages_for(pos) pages; a boundary resume
            # allocates one page beyond it, filled by the very next decode
            # write
            self.cache = kv_cache.swap_in_slot(
                self.cache, st.data, s,
                ids[: pages_for(st.pos, self.page_size)], self.paged_mask)
            if self.mesh is not None:
                from repro.launch import sharding as shardlib
                self.cache = shardlib.repin_serve_cache(self.mesh, self.cache)
        self.preempted.remove(req)
        del self._swap[req.rid]
        req.state = RUNNING
        self.slot_req[s] = req
        self.slot_pos[s] = st.pos
        self.stats["resumes"] += 1
        self._epoch += 1
        return True

    # -- serving loop ----------------------------------------------------------

    def _retire(self, skip=frozenset(), quiet=frozenset()):
        """Clear completed slots: out of budget, cache full, or EOS sampled.

        `skip`: slots with a token still in flight (dispatch-ahead build) —
        their out list is one short of the truth, so they must not be judged
        here (the will_retire prediction covers them). `quiet`: slots whose
        retirement the prepared plan already predicted — retiring them does
        NOT bump the epoch, so the prediction keeps the plan consumable.
        PREFILLING slots never retire here: slot_pos is their chunk clock,
        not a decode position (an n == cache_len prompt would falsely trip
        the cache-full test mid-prefill)."""
        for s, req in enumerate(self.slot_req):
            if req is None or s in skip or req.state == PREFILLING:
                continue
            eos = False
            if req.eos is not None and req.eos in req.out:
                # a multi-token accept can land tokens PAST the stop token
                # in one tick; generation ends at EOS, so truncate there and
                # retire now. (The old `out[-1] == eos` test only caught a
                # final-position EOS and kept decoding past a mid-batch one.)
                del req.out[req.out.index(req.eos) + 1:]
                eos = True
            if (len(req.out) >= req.max_new or eos
                    or self.slot_pos[s] >= self.cache_len - 1):
                req.done = True
                self.completed.append(req)
                if self.paged:
                    self.pt.retire(s)
                self.slot_req[s] = None
                self.slot_pos[s] = 0
                self._prefill_ctx.pop(s, None)
                if s not in quiet:
                    self._epoch += 1

    def _prepare_pages(self, skip=frozenset(), lookahead=None):
        """Per-tick page work, most-important slot first: CoW-fork every
        shared page the tick will write into, then extend coverage through
        the write range. When the pool runs dry (--preempt only; the
        conservative reservation makes it unreachable otherwise), evict
        strictly-lower-priority victims — or the claimant itself when none
        remain. PREFILLING slots need no work (all prompt pages were claimed
        at admission; chunks never CoW — shared pages are write-masked);
        `skip` holds predicted-retire slots, which will never write again.

        `lookahead` (speculative ticks): slot -> token positions the tick
        writes, [pos, pos+la). The draft chain and the verify step both
        scribble across the whole range before the accept decision, so every
        shared held page in it must fork NOW — a shared page left in place
        would take rejected-draft bytes a co-owner could read. Default la=1
        is exactly the sequential single-write behavior."""
        order = sorted((s for s, r in enumerate(self.slot_req)
                        if r is not None and r.state == RUNNING
                        and s not in skip),
                       key=lambda v: self._prio(self.slot_req[v]))
        for s in order:
            req = self.slot_req[s]
            if req is None:
                continue           # preempted by a more important slot's claim
            pos = int(self.slot_pos[s])
            la = 1 if lookahead is None else int(lookahead.get(s, 1))
            last_pg = (pos + la - 1) // self.page_size
            need = max(0, (last_pg + 1) - int(self.pt.held[s]))
            forkable = []
            if self.prefix_share:
                for idx in range(pos // self.page_size,
                                 min(last_pg + 1, int(self.pt.held[s]))):
                    tokpos = max(pos, idx * self.page_size)
                    if self.pt.cow_pending(s, tokpos):
                        forkable.append(tokpos)
                need += len(forkable)
            if need > self.pt.free_pages:
                if not self.preempt or not self._make_room(need, self._prio(req)):
                    if self.preempt:
                        self._preempt(s)   # no cheaper victim: swap itself out
                        continue
                    raise RuntimeError(
                        "page pool exhausted mid-decode without --preempt "
                        "(admission reservation should have prevented this)")
            for tokpos in forkable:
                fork = self.pt.fork_cow(s, tokpos)
                if fork is not None:
                    src, dst = fork
                    self.cache = self._cow(self.cache, jnp.int32(src),
                                           jnp.int32(dst))
                    self.stats["cow_forks"] += 1
                    self._epoch += 1   # table remap: fences any stale plan
            self.pt.extend(s, pos + la)

    def _plan_chunk(self) -> dict | None:
        """Operands for this tick's prefill chunk: the most-important
        PREFILLING slot advances by min(chunk_tokens, remaining prompt).
        `read` is the slot's real page row (attention must see shared-prefix
        KV); `write` NULLs the shared pages so the chunk can never scribble
        on a co-owner's bytes (its own tokens inside a fully-shared page are
        already there, byte-identically, from whoever built the page)."""
        cands = [s for s, r in enumerate(self.slot_req)
                 if r is not None and r.state == PREFILLING]
        if not cands:
            return None
        s = min(cands, key=lambda v: self._prio(self.slot_req[v]))
        req = self.slot_req[s]
        pctx = self._prefill_ctx[s]
        n, C = pctx["n"], self.chunk_tokens
        covered = int(self.slot_pos[s])
        creal = min(C, n - covered)
        toks = np.zeros((1, C), np.int32)
        toks[0, :creal] = req.prompt[covered:covered + creal]
        final = covered + creal >= n
        read = self.pt.table[s].copy()
        write = read.copy()
        if pctx["shared"] is not None:
            sh = np.asarray(pctx["shared"], bool)
            write[:len(sh)][sh] = NULL_PAGE
        return {"slot": s, "tokens": toks, "pos0": covered, "nreal": creal,
                "final": final, "last_idx": creal - 1 if final else 0,
                "read": read, "write": write}

    def _build_plan(self, pending=frozenset()) -> _Plan:
        """One tick's scheduling: admit/resume -> retire -> predict retires
        of in-flight slots -> page work (CoW fork, extend, preempt) -> the
        masked device table and chunk operands. `pending` holds slots whose
        token is still on device (dispatch-ahead): they are skipped by the
        real retire pass (their out list is one short) and instead retired
        *predictively* — excluded from the next actives, retired quietly at
        fix-up. EOS cannot be predicted; it retires loudly and fences.

        The epoch is snapshotted at the END: every mutation this build
        itself makes (admissions, forks, preemptions...) is part of the
        plan, not a reason to fence it."""
        self._admit()
        self._retire(skip=pending)
        will_retire = []
        for s in pending:
            req = self.slot_req[s]
            if req is None or req.state != RUNNING:
                continue
            if (len(req.out) + 1 >= req.max_new
                    or self.slot_pos[s] >= self.cache_len - 1):
                will_retire.append(s)
        skip = frozenset(will_retire)
        if self.paged:
            with _span("pages"):
                self._prepare_pages(skip=skip)
            # physical pool pressure (aliasing-aware: shared pages count
            # once) — what the slab layout would need is Σ per-slot coverage
            self.stats["peak_pages"] = max(
                self.stats["peak_pages"],
                self.pt.usable_pages - self.pt.free_pages)
        active = [s for s, r in enumerate(self.slot_req)
                  if r is not None and r.state == RUNNING and s not in skip]
        reqs = [self.slot_req[s] for s in active]
        table = None
        if self.paged:
            # mask non-decoding rows to NULL: a PREFILLING slot's pages must
            # not take the decode write at its chunk-clock position, and the
            # inert phys-slot padding rows never had pages. NULL rows write
            # scratch page 0 and read nothing valid (pos 0, token 0).
            table = self.pt.table.copy()
            rowmask = np.ones(len(table), bool)
            rowmask[active] = False
            table[rowmask] = NULL_PAGE
        chunk = self._plan_chunk() if self.chunk_tokens else None
        return _Plan(epoch=self._epoch, active=active, reqs=reqs,
                     table=table, chunk=chunk,
                     will_retire=tuple(will_retire))

    def _spec_step(self):
        """One self-speculative tick: DRAFT up to spec_k-1 tokens per slot
        with the truncated-plane context, VERIFY them in one full-precision
        multi-token step, accept the longest exactly-matching prefix plus
        the first corrected token.

        Token-exactness: every ACCEPTED token is sampled (same stateless
        (seed, index) rng) from verify logits computed over exactly the
        inputs the sequential path would have fed — row t of the verify
        chunk consumes [last_token, draft_0..draft_{t-1}], and the accept
        loop only reaches row t when all those drafts matched the verify
        samples (transformer.decode_verify). The draft decides HOW MANY
        rows are usable, never WHAT tokens land.

        The draft chain threads a throwaway cache lineage: reduced-precision
        draft KV feeds later draft steps but never survives — verify starts
        from the pre-draft cache and rewrites the whole [pos, pos+k) range
        with exact KV, so rejected-draft bytes cannot leak into any future
        read. Positions past the accepted point hold garbage from rejected
        inputs; they are overwrite-before-read safe (the next tick scatters
        from the rewound position before its gather, and its causal mask
        never reaches past its own rows)."""
        self._admit()
        self._retire()
        # per-slot window: never past the request budget or the final cache
        # slot (the _retire above guarantees >= 1 for every RUNNING slot)
        keff = {}
        for s, r in enumerate(self.slot_req):
            if r is not None and r.state == RUNNING:
                keff[s] = max(1, min(self.spec_k, r.max_new - len(r.out),
                                     self.cache_len - 1 - int(self.slot_pos[s])))
        with _span("pages"):
            self._prepare_pages(lookahead=keff)
        active = [s for s in sorted(keff) if self.slot_req[s] is not None
                  and self.slot_req[s].state == RUNNING]
        self.stats["peak_pages"] = max(
            self.stats["peak_pages"],
            self.pt.usable_pages - self.pt.free_pages)
        if not active:
            return bool(self.queue or self.preempted
                        or any(r is not None for r in self.slot_req))
        reqs = {s: self.slot_req[s] for s in active}
        base = {s: int(self.slot_pos[s]) for s in active}
        self.pos_trace.append(self.slot_pos[active].copy())
        table = self.pt.table.copy()
        rowmask = np.ones(len(table), bool)
        rowmask[active] = False
        table[rowmask] = NULL_PAGE
        # -- draft: sequential truncated-plane decode steps, batched over
        # the slots still inside their window (finished rows mask to NULL)
        drafts = {s: [] for s in active}
        cur = {s: reqs[s].out[-1] for s in active}
        dcache = self.cache
        for j in range(self.spec_k - 1):
            live = [s for s in active if j < keff[s] - 1]
            if not live:
                break
            tokens = np.zeros((self.phys_slots, 1), np.int32)
            pos = np.zeros(self.phys_slots, np.int32)
            dtab = table.copy()
            dmask = np.ones(len(dtab), bool)
            dmask[live] = False
            dtab[dmask] = NULL_PAGE
            for s in live:
                tokens[s, 0] = cur[s]
                pos[s] = base[s] + j
            dlogits, dcache = self._pop_moe(
                self._draft(self.params, dcache, jnp.asarray(tokens),
                            jnp.asarray(pos), jnp.asarray(dtab)),
                count=False)   # verify re-routes these positions exactly
            with _span("sync", what="draft"):
                rows = np.asarray(dlogits[:, 0])
            for s in live:
                r = reqs[s]
                d = sample_token(rows[s], r.temperature, r.seed,
                                 len(r.out) + j)
                drafts[s].append(d)
                cur[s] = d
        # -- verify: one chunk-algebra step over [last_token, drafts...] per
        # slot, writing exact KV across the whole window (read and write
        # tables coincide: the lookahead fork above made every page in the
        # write range exclusively owned)
        tokens = np.zeros((self.phys_slots, self.spec_k), np.int32)
        pos0 = np.zeros(self.phys_slots, np.int32)
        nreal = np.zeros(self.phys_slots, np.int32)
        for s in active:
            row = [reqs[s].out[-1]] + drafts[s]
            tokens[s, :len(row)] = row
            pos0[s] = base[s]
            nreal[s] = keff[s]
        vlogits, self.cache = self._pop_moe(self._verify(
            self.params, self.cache, jnp.asarray(tokens), jnp.asarray(pos0),
            jnp.asarray(table), jnp.asarray(table), jnp.asarray(nreal)))
        with _span("sync", what="verify"):
            vrows = np.asarray(vlogits)
        self.stats["spec_ticks"] += 1
        for s in active:
            r = reqs[s]
            emitted = []
            n_acc = 0
            for t in range(keff[s]):
                v = sample_token(vrows[s, t], r.temperature, r.seed,
                                 len(r.out) + t)
                emitted.append(v)
                if t < len(drafts[s]):
                    if drafts[s][t] != v:
                        break
                    n_acc += 1
            self.stats["spec_proposed"] += len(drafts[s])
            self.stats["spec_accepted"] += n_acc
            self.stats["spec_emitted"] += len(emitted)
            r.out.extend(emitted)
            # exact-KV coverage = inputs consumed by the accepted rows; the
            # last emitted token was never fed, so the next tick feeds it at
            # exactly this position (garbage beyond is overwritten there)
            self.slot_pos[s] = base[s] + len(emitted)
        self._epoch += 1
        with _span("retire"):
            self._retire()   # truncates at a mid-batch EOS before retiring
            self._drain_moe()
        return bool(any(r is not None for r in self.slot_req) or self.queue
                    or self.preempted)

    def step(self):
        """One server tick: consume the prepared plan (or build one) ->
        dispatch the fused decode and the prefill chunk -> optimistically
        advance host state and build the NEXT plan while the device works ->
        fix-up (sample the landed tokens, retire).

        Dispatch-ahead fence: the prepared plan is consumed iff its epoch
        snapshot still matches — nothing (submit, EOS/unpredicted retire,
        preemption, resume, fork) mutated the scheduler after it was built.
        A mismatch trips stats["fences"] and rebuilds synchronously; a match
        is stats["plan_hits"].

        The pre-decode retire pass inside _build_plan clears requests that
        are already complete at admission (max_new == 1, or a prompt that
        fills the cache) so they never reach the decode step with nowhere
        left to write.

        Each call is one `serve.step` host span holding the tick's phase
        spans (docs/SERVING.md, "Tracing a live server")."""
        if self.spec:
            with _span("step", plan_hit=0):
                return self._spec_step()
        plan = None
        if self._prepared is not None:
            if self._prepared.epoch == self._epoch:
                plan = self._prepared
                self.stats["plan_hits"] += 1
            else:
                self.stats["fences"] += 1
            self._prepared = None
        with _span("step", plan_hit=int(plan is not None)):
            return self._tick(plan)

    def _tick(self, plan: _Plan | None):
        """`step` once the prepared plan is consumed (`plan`) or fenced
        (None: build one now)."""
        if plan is None:
            with _span("plan", ahead=0):
                plan = self._build_plan()
        active, chunk = plan.active, plan.chunk
        if not active and chunk is None:
            return bool(self.queue or self.preempted
                        or any(r is not None for r in self.slot_req))
        # -- dispatch: decode first, then the chunk. Functional cache
        # chaining orders the device ops; the two touch disjoint pages (or
        # read-only-shared ones — decode write pages are pre-forked), so
        # either order is token-exact; decode-first matches the sequential
        # oracle's schedule.
        logits = greedy = nxt_dev = None
        c_logits = None
        chunk_req = None
        with _span("dispatch", slots=len(active),
                   chunk=int(chunk is not None)):
            if active:
                tokens = np.zeros((self.phys_slots, 1), np.int32)
                pos = np.zeros(self.phys_slots, np.int32)
                for i, s in enumerate(active):
                    tokens[s, 0] = plan.reqs[i].out[-1]
                    pos[s] = self.slot_pos[s]
                self.pos_trace.append(self.slot_pos[active].copy())
                if self.paged:
                    logits, self.cache = self._pop_moe(self._decode(
                        self.params, self.cache, jnp.asarray(tokens),
                        jnp.asarray(pos), jnp.asarray(plan.table)))
                else:
                    logits, self.cache = self._pop_moe(self._decode(
                        self.params, self.cache, jnp.asarray(tokens),
                        jnp.asarray(pos)))
                greedy = not any(r.temperature > 0 for r in plan.reqs)
                if greedy:
                    # argmax on device, transfer (slots,) ints — not the
                    # whole vocab matrix (np and jnp argmax both break ties
                    # to the lowest index, so this equals sample_token at
                    # temp 0)
                    nxt_dev = jnp.argmax(logits[:, 0], axis=-1)
            if chunk is not None:
                cs = chunk["slot"]
                chunk_req = self.slot_req[cs]
                self.stats["chunk_ticks"] += 1
                c_logits, self.cache = self._pop_moe(self._chunk(
                    self.params, self.cache, jnp.asarray(chunk["tokens"]),
                    jnp.asarray([chunk["pos0"]], jnp.int32),
                    jnp.asarray(chunk["read"])[None],
                    jnp.asarray(chunk["write"])[None],
                    jnp.asarray([chunk["nreal"]], jnp.int32),
                    jnp.asarray([chunk["last_idx"]], jnp.int32)))
        # -- optimistic host advance (deterministic consequences of the
        # dispatch — token VALUES stay unknown until fix-up)
        for s in active:
            self.slot_pos[s] += 1
        if chunk is not None:
            cs = chunk["slot"]
            self.slot_pos[cs] = chunk["pos0"] + chunk["nreal"]
            pctx = self._prefill_ctx[cs]
            if self.prefix_share and pctx["keys"] is not None:
                # progressive share-index registration: pages whose keyed
                # coverage the chunks now reach become mappable by later
                # admissions (deferred from admit_shared)
                self.pt.index_pages(cs, pctx["keys"],
                                    int(self.slot_pos[cs]))
            if chunk["final"]:
                chunk_req.state = RUNNING
                self._prefill_ctx.pop(cs, None)
        # -- dispatch-ahead: overlap next tick's host scheduling with this
        # tick's device work (the jitted calls above returned futures)
        if self.dispatch_ahead:
            pend = set(active)
            if chunk is not None and chunk["final"]:
                pend.add(chunk["slot"])
            with _span("plan", ahead=1):
                self._prepared = self._build_plan(pending=frozenset(pend))
        # -- fix-up: the device tokens land in the Request objects CAPTURED
        # at dispatch (plan.reqs) — a pending slot may have been preempted
        # (or its slot re-assigned) during the ahead build
        if active:
            if greedy:
                with _span("sync", what="decode"):
                    nxt = np.asarray(nxt_dev)
                for i, s in enumerate(active):
                    self._deliver(plan.reqs[i], int(nxt[s]))
            else:
                with _span("sync", what="decode"):
                    rows = np.asarray(logits[:, 0])    # (slots, V) to host
                with _span("sample", rows=len(active)):
                    for i, s in enumerate(active):
                        r = plan.reqs[i]
                        self._deliver(r, self._sample(r, rows[s]))
        if chunk is not None and chunk["final"]:
            with _span("sync", what="chunk"):
                row = np.asarray(c_logits)[0, 0]
            self._deliver(chunk_req, self._sample(chunk_req, row))
        quiet = (frozenset(self._prepared.will_retire)
                 if self._prepared is not None else frozenset())
        with _span("retire"):
            self._retire(quiet=quiet)
            self._drain_moe()
        return bool(any(r is not None for r in self.slot_req) or self.queue
                    or self.preempted)

    def _deliver(self, req: Request, tok: int):
        """Append a landed token; finish a request that completed while
        PREEMPTED (its slot was swapped out during the ahead build after its
        last token dispatched — _retire only sees slotted requests, and a
        resumed overrun past EOS would be wrong)."""
        req.out.append(tok)
        if req.state == PREEMPTED and (
                len(req.out) >= req.max_new
                or (req.eos is not None and tok == req.eos)):
            req.done = True
            self.completed.append(req)
            self.preempted.remove(req)
            del self._swap[req.rid]
            self._epoch += 1   # the prepared plan may have planned its resume

    def run(self):
        ticks = 0
        while (self.queue or self.preempted
               or any(r is not None for r in self.slot_req)):
            self.step()
            ticks += 1
        return ticks

    def flush_tier(self):
        """Demote every parked device-tier page to the store and push the
        store's host tier to disk — the clean-shutdown path that makes
        indexed prefixes survive a restart (tests/CI kill-and-restart
        smoke). No-op without a tiered table."""
        if hasattr(self.pt, "flush_cached"):
            self.pt.flush_cached()
            store = getattr(self.pt, "store", None)
            if store is not None:
                store.flush()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--policy", default=None)
    ap.add_argument("--backend", default="jnp", choices=("jnp", "pallas"),
                    help="GEMM backend half of each layer's OperatingPoint "
                         "(precisions come from the policy per layer; both "
                         "backends route through kernels.dispatch.qgemm)")
    ap.add_argument("--impl", default="popcount",
                    choices=("popcount", "mxu", "planes"),
                    help="GEMM formulation half of the OperatingPoint: "
                         "popcount/mxu pick the binary/ternary cell "
                         "(int8/int4/mixed cells are formulation-agnostic); "
                         "'planes' routes int4/int8-weight layers through "
                         "the bit-plane-composed cells (per-layer fallback "
                         "to popcount where no plane cell exists)")
    ap.add_argument("--paged-attn", default="auto",
                    choices=("auto", "gather", "fused"),
                    help="paged decode-attention read path: 'auto' runs the "
                         "fused Pallas page-walk kernel "
                         "(kernels.paged_attn) iff --backend pallas, "
                         "'fused'/'gather' force it on/off (gather = the "
                         "jnp oracle path)")
    ap.add_argument("--tune", default=None, metavar="TUNE_JSON",
                    help="kernels.dispatch.TuneTable JSON overriding the "
                         "shipped per-cell Tile table (autotuned block "
                         "shapes per operating point)")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="tensor-parallel serving: build a ('data','model') "
                         "mesh of this shape and run qgemm under shard_map "
                         "(e.g. --mesh 2,4; needs data*model visible devices "
                         "— on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    grp = ap.add_mutually_exclusive_group()
    grp.add_argument("--paged", dest="paged", action="store_true", default=True,
                     help="paged KV cache (default): block pool + page table")
    grp.add_argument("--contiguous", dest="paged", action="store_false",
                     help="per-slot slab KV cache (reference layout; keeps "
                          "the conservative slot/lifetime admission)")
    ap.add_argument("--page-size", type=int, default=32)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool size; < slots*cache_len/page_size oversubscribes "
                         "and admission throttles on the page budget")
    ap.add_argument("--prefix-share", action="store_true",
                    help="hash-index full prompt pages so identical prefixes "
                         "map one set of physical pages (copy-on-write on "
                         "decode divergence)")
    ap.add_argument("--preempt", action="store_true",
                    help="admit on prompt pages only; when the pool runs dry "
                         "mid-decode, swap the lowest-priority running "
                         "request to a host slab and resume it later")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="fold prefill into the decode tick: one chunk of "
                         "this many prompt tokens runs per tick next to the "
                         "fused decode (0 = whole-prompt bucketed prefill). "
                         "Token-exact and KV byte-identical vs whole-prompt; "
                         "needs --paged")
    ap.add_argument("--spec-draft", default=None, metavar="KIND[:DEPTH]",
                    help="self-speculative decoding: draft next tokens with "
                         "a truncated formulation over the SAME packed "
                         "weights ('planes:1' = sign-plane-only draft), "
                         "verify with one full-precision multi-token step "
                         "per tick; token-exact vs sequential decoding. "
                         "Needs --paged; exclusive with --chunk-tokens")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative window: draft K-1 tokens and verify "
                         "K rows per tick (with --spec-draft)")
    ap.add_argument("--moe-ep", dest="moe_ep", action="store_true",
                    default=True,
                    help="expert-parallel MoE serving (default, MoE archs "
                         "under --mesh): shard expert stacks over the "
                         "'model' axis and run the grouped expert dispatch "
                         "(each shard computes only its local experts); "
                         "token-exact vs the dense expert vmap")
    ap.add_argument("--no-moe-ep", dest="moe_ep", action="store_false",
                    help="keep the replicated dense expert vmap under "
                         "--mesh (oracle / fallback path)")
    ap.add_argument("--no-dispatch-ahead", dest="dispatch_ahead",
                    action="store_false", default=True,
                    help="disable double buffering (host prepares tick N+1 "
                         "while tick N runs on device; an epoch fence "
                         "rebuilds when a submit/EOS/preemption invalidates "
                         "the prepared plan)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token id: a request retires the step this "
                         "token is sampled (pages free immediately; later "
                         "steps neither sample nor write KV for it)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy); "
                         "stateless rng keyed by (seed, token index)")
    ap.add_argument("--jit-budget", type=int, default=None,
                    help="fail (exit 1) if the total trace-time compile "
                         "signatures (prefill buckets + decode + cow + "
                         "chunk) exceed this — the CI recompile-regression "
                         "gate")
    args = ap.parse_args(argv)
    configure_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.policy:
        cfg = dataclasses.replace(cfg, policy=args.policy)

    mesh = None
    if args.mesh:
        d, m = (int(v) for v in args.mesh.split(","))
        if d * m > len(jax.devices()):
            raise SystemExit(
                f"--mesh {args.mesh} needs {d * m} devices, have "
                f"{len(jax.devices())} (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={d * m} on CPU)")
        mesh = make_mesh((d, m), ("data", "model"))
        print(f"mesh: data={d} x model={m} ({d * m} devices); "
              f"qgemm under shard_map, paged pool sharded over data")

    sparams, train_b = serve_params(
        cfg, mesh=mesh,
        plane_twins=args.spec_draft is not None or args.impl == "planes")
    serve_b = sum(x.nbytes for x in jax.tree.leaves(sparams))
    print(f"packed weights: {train_b/2**20:.1f} MiB -> {serve_b/2**20:.1f} MiB "
          f"({train_b/serve_b:.1f}x smaller, policy={cfg.policy})")

    tune = None
    if args.tune:
        from repro.kernels.dispatch import TuneTable
        tune = TuneTable.load(args.tune)
        print(f"tune table: {args.tune} ({len(tune.tiles)} cells, "
              f"source: {tune.source})")

    srv = Server(cfg, sparams, slots=args.slots, cache_len=args.cache_len,
                 paged=args.paged, page_size=args.page_size,
                 num_pages=args.num_pages, mesh=mesh,
                 prefix_share=args.prefix_share, preempt=args.preempt,
                 chunk_tokens=args.chunk_tokens,
                 dispatch_ahead=args.dispatch_ahead,
                 spec_draft=args.spec_draft, spec_k=args.spec_k,
                 moe_ep=args.moe_ep,
                 ctx=ModelCtx(mode="serve", backend=args.backend,
                              impl=args.impl, tune=tune,
                              paged_attn=args.paged_attn))
    if args.chunk_tokens and not srv.chunk_tokens:
        print("chunked prefill disabled: arch needs exact-length prefill "
              "or int8 KV (fell back to whole-prompt buckets)")
    if args.spec_draft and not srv.spec:
        print("speculative decoding disabled: arch needs exact-length "
              "prefill or int8 KV (verify rides the chunk path); "
              "fell back to sequential decode")
    if args.paged:
        fused = (args.paged_attn == "fused"
                 or (args.paged_attn == "auto" and args.backend == "pallas"))
        print(f"decode attention: {'fused pallas page-walk kernel' if fused else 'jnp gather path'} "
              f"(--paged-attn {args.paged_attn}, --backend {args.backend})")
    rng = np.random.default_rng(0)
    # with --prefix-share, every request repeats a common prompt prefix
    # (page-aligned so it aliases whole pages) and request 1 duplicates
    # request 0 EXACTLY — the duplicate aliases the partial boundary page
    # too, so the co-running pair forces a CoW fork on its first divergent
    # decode write (exact-coverage keys mean prefix-only overlap never
    # shares the boundary page, hence never forks)
    shared_prefix = (rng.integers(0, cfg.vocab,
                                  size=(args.page_size,)).astype(np.int32)
                     if args.prefix_share else None)
    t0 = time.time()
    first = None
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab,
                              size=(rng.integers(4, 17),)).astype(np.int32)
        if shared_prefix is not None:
            prompt = np.concatenate([shared_prefix, prompt[:8]])
            if i == 0:
                first = prompt
            elif i == 1:
                prompt = first.copy()
        srv.submit(Request(i, prompt, args.max_new,
                           temperature=args.temperature, seed=i,
                           eos=args.eos_id))
    ticks = srv.run()
    dt = time.time() - t0
    total_new = sum(len(r.out) for r in srv.completed)
    layout = "paged" if args.paged else "contiguous"
    dev = jax.devices()[0]
    print(f"served {len(srv.completed)} requests, {total_new} tokens, "
          f"{ticks} ticks, {dt:.1f}s on {dev.platform} ({dev.device_kind}), "
          f"{layout} cache — wall time including compiles, not a benchmark")
    total_sigs = sum(srv.compile_counts.values())
    print(f"jit signatures: prefill={srv.compile_counts['prefill']} "
          f"(buckets={list(srv.buckets)}), decode={srv.compile_counts['decode']}, "
          f"cow={srv.compile_counts['cow']}, "
          f"chunk={srv.compile_counts['chunk']}, "
          f"draft={srv.compile_counts['draft']}, "
          f"verify={srv.compile_counts['verify']}, total={total_sigs}")
    if srv.chunk_tokens:
        print(f"chunked prefill: {srv.stats['chunk_ticks']} chunk ticks "
              f"(--chunk-tokens {srv.chunk_tokens})")
    if srv.spec:
        prop = srv.stats["spec_proposed"]
        acc = srv.stats["spec_accepted"]
        sticks = max(srv.stats["spec_ticks"], 1)
        print(f"speculative: {srv.stats['spec_ticks']} spec ticks, "
              f"accept-rate {acc}/{prop} ({acc / max(prop, 1):.0%}), "
              f"{srv.stats['spec_emitted'] / sticks:.2f} tokens/tick "
              f"(--spec-draft {args.spec_draft}, --spec-k {srv.spec_k})")
    if srv.dispatch_ahead:
        print(f"dispatch-ahead: {srv.stats['plan_hits']} plan hits, "
              f"{srv.stats['fences']} fences")
    if args.paged:
        print(f"page pool: {srv.pt.usable_pages} usable pages x "
              f"{srv.pt.page_size} tokens, {srv.pt.free_pages} free at exit")
    if cfg.n_experts:
        routed = max(srv.stats["moe_routed"], 1)
        et = srv.stats["moe_expert_tokens"]
        util = [f"{v / max(sum(et), 1):.2f}" for v in et]
        mode = "EP grouped dispatch" if srv.ctx.ep is not None \
            else "dense expert vmap"
        print(f"moe: {mode}, routed={srv.stats['moe_routed']} "
              f"dropped={srv.stats['moe_dropped']} "
              f"(drop-rate {srv.stats['moe_dropped'] / routed:.1%}), "
              f"expert util {util}")
    if args.prefix_share or args.preempt:
        print(f"scheduler: shared_pages={srv.stats['shared_pages']} "
              f"cow_forks={srv.stats['cow_forks']} "
              f"preemptions={srv.stats['preemptions']} "
              f"resumes={srv.stats['resumes']}")
    if args.jit_budget is not None and total_sigs > args.jit_budget:
        raise SystemExit(f"jit budget exceeded: {total_sigs} trace-time "
                         f"signatures > committed budget {args.jit_budget}")
    return srv


if __name__ == "__main__":
    main()
