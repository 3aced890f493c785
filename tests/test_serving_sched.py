"""Scheduler lockdown: prefix sharing, copy-on-write, preemption + swap must
all be TOKEN-EXACT against the sequential single-request oracle.

Correctness here is adversarial by construction: a missed CoW fork lets one
request's decode writes corrupt a co-owner's shared page; a swap that drops
or rounds a byte resumes a request in a subtly different state; a refcount
bug hands a live page to a newcomer. None of those look like crashes — they
look like *plausible but different tokens*, so every test demands bit-exact
token equality, not a tolerance.

Determinism notes that make exactness possible:
  * sampling is stateless (`models.common.sample_token`, rng keyed by
    (seed, token index)) — a request's token i is a pure function of its
    logits, seed and i, independent of batching/preemption history;
  * a token's KV depends only on the token-id prefix (causal attention, no
    dropout at serve), so shared pages hold bit-identical KV by definition;
  * swap slabs are numpy copies in the pool dtype — no conversion.

Under pure greedy decode two requests with identical prompts emit identical
tokens, which would make a broken CoW *invisible* (the corrupting writes
write the same bytes). The CoW tests therefore sample with temperature > 0
and distinct seeds: continuations diverge right at the shared boundary page,
and a missing fork shows up as a token mismatch.
"""
import dataclasses
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import PREEMPTED, RUNNING, WAITING, Request, Server
from repro.models import transformer
from repro.models.common import ModelCtx, sample_token

MAX_NEW = 4
CACHE_LEN = 32
PAGE_SIZE = 4
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def _built(policy: str):
    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(), policy=policy)
    sp = transformer.build_specs(cfg)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    sparams = transformer.pack_for_serve(params, cfg)
    return cfg, sp, sparams


def _shared_prefix_prompts(cfg, *, prefix_len=8, tails=(2, 2, 2), seed=17,
                           duplicate_first=True):
    """Prompts sharing a common prefix; optionally one exact duplicate (the
    duplicate aliases the *partial* boundary page too — the CoW case)."""
    rng = np.random.default_rng(seed)
    common = rng.integers(0, cfg.vocab, size=(prefix_len,)).astype(np.int32)
    prompts = [np.concatenate([common,
                               rng.integers(0, cfg.vocab, size=(t,)).astype(np.int32)])
               for t in tails]
    if duplicate_first:
        prompts.append(prompts[0].copy())
    return prompts


def _reference(cfg, sp, sparams, ctx, prompt, max_new, *, temperature=0.0,
               seed=0):
    """Single-request decode on the seed-validated contiguous scalar-pos
    path, sampling with the same stateless rng the server uses."""
    logits, cache = transformer.prefill(sparams, jnp.asarray(prompt)[None], sp,
                                        ctx, cache_len=CACHE_LEN)
    out = [sample_token(np.asarray(logits[0, -1]), temperature, seed, 0)]
    pos = len(prompt)
    while len(out) < max_new:
        l, cache = transformer.decode_step(
            sparams, cache, jnp.asarray([[out[-1]]], jnp.int32),
            jnp.int32(pos), sp, ctx)
        out.append(sample_token(np.asarray(l[0, 0]), temperature, seed,
                                len(out)))
        pos += 1
    return out


def _serve(cfg, sparams, ctx, reqs, **kw):
    srv = Server(cfg, sparams, cache_len=CACHE_LEN, page_size=PAGE_SIZE,
                 paged=True, ctx=ctx, **kw)
    for r in reqs:
        srv.submit(r)
    srv.run()
    assert len(srv.completed) == len(reqs)
    # the scheduler always drains completely and leaks nothing
    assert not srv.preempted and not srv._swap
    assert srv.pt.free_pages == srv.pt.usable_pages
    return srv


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("policy", ["binary", "ternary", "int8"])
def test_share_and_preempt_token_exact(policy, backend):
    """Shared-prefix traffic (incl. one exact-duplicate prompt) through a
    page-tight server with --prefix-share AND --preempt: token-for-token
    identical to the sequential oracle for all three W&A policies on both
    qgemm backends — while pages really alias and the jit discipline holds."""
    cfg, sp, sparams = _built(policy)
    ctx = ModelCtx(mode="serve", backend=backend, dtype=jnp.float32)
    prompts = _shared_prefix_prompts(cfg)
    want = [_reference(cfg, sp, sparams, ctx, p, MAX_NEW) for p in prompts]
    reqs = [Request(i, p, MAX_NEW) for i, p in enumerate(prompts)]
    # 8 usable pages: every request's lifetime alone needs 4, so nothing
    # would co-run without sharing; sharing keeps 2+ slots busy
    srv = _serve(cfg, sparams, ctx, reqs, slots=3, num_pages=9,
                 prefix_share=True, preempt=True)
    assert srv.stats["shared_pages"] > 0, srv.stats
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, (policy, backend, i, got[i], w)
    # jit discipline survives sharing/CoW/preemption: one decode signature,
    # bucketed prefill, at most one CoW-copy signature
    assert srv.compile_counts["decode"] == 1, srv.compile_counts
    assert srv.compile_counts["cow"] <= 1, srv.compile_counts
    assert srv.compile_counts["prefill"] <= len(srv.buckets)


def test_cow_isolates_sampled_divergence():
    """Three requests with IDENTICAL prompts but different sampling seeds:
    admission aliases all their pages (including the partial boundary page),
    the first divergent decode write forces a CoW fork, and every request
    must still match its own solo oracle. Without the fork, co-owners would
    overwrite each other's boundary page with *different* bytes — this is
    the test a missing/broken copy-on-write cannot pass."""
    cfg, sp, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, cfg.vocab, size=(6,)).astype(np.int32)
    reqs = [Request(i, prompt.copy(), 6, temperature=1.0, seed=100 + i)
            for i in range(3)]
    srv = _serve(cfg, sparams, ctx, reqs, slots=3, prefix_share=True)
    assert srv.stats["shared_pages"] >= 2, srv.stats   # full + partial page
    assert srv.stats["cow_forks"] >= 1, srv.stats
    outs = {r.rid: r.out for r in srv.completed}
    assert len({tuple(o) for o in outs.values()}) == 3, \
        f"seeds should diverge: {outs}"
    for i in range(3):
        want = _reference(cfg, sp, sparams, ctx, prompt, 6,
                          temperature=1.0, seed=100 + i)
        assert outs[i] == want, (i, outs[i], want)


@pytest.mark.parametrize("chunk_tokens", [3, 5])
def test_chunked_share_preempt_token_exact(chunk_tokens):
    """Chunked prefill under the full scheduler gauntlet: shared-prefix
    traffic (incl. an exact duplicate) with --prefix-share AND --preempt
    over a page-tight pool, sampling at temperature > 0 so CoW divergence
    is forced — token-exact vs the sequential oracle. The chunk sizes do
    not divide the prompt lengths (padded final chunk) and straddle page
    boundaries; deferred share-index registration must still alias pages
    (shared_pages > 0) even though the prefix is built chunk by chunk, and
    the whole run compiles zero prefill-bucket signatures."""
    cfg, sp, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    prompts = _shared_prefix_prompts(cfg)
    want = [_reference(cfg, sp, sparams, ctx, p, MAX_NEW, temperature=0.9,
                       seed=50 + i) for i, p in enumerate(prompts)]
    reqs = [Request(i, p, MAX_NEW, temperature=0.9, seed=50 + i)
            for i, p in enumerate(prompts)]
    srv = _serve(cfg, sparams, ctx, reqs, slots=3, num_pages=9,
                 prefix_share=True, preempt=True, chunk_tokens=chunk_tokens)
    assert srv.stats["chunk_ticks"] > 0, srv.stats
    assert srv.stats["shared_pages"] > 0, srv.stats
    assert srv.compile_counts["prefill"] == 0, srv.compile_counts
    assert srv.compile_counts["chunk"] == 1, srv.compile_counts
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, (chunk_tokens, i, got[i], w)


def test_preemption_swaps_out_and_resumes_token_exact():
    """A pool too small for two decode lifetimes with --preempt: both
    requests admit immediately (prompt-only admission), the pool runs dry
    mid-decode, the younger request is swapped out to the host slab and
    later swapped back in — and both outputs are bit-identical to the
    sequential oracle. Also checks the request-state lifecycle."""
    cfg, sp, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, size=(8,)).astype(np.int32)
               for _ in range(2)]
    max_new = 12    # lifetime 8+12-1=19 tokens -> 5 pages each; 6 usable
    want = [_reference(cfg, sp, sparams, ctx, p, max_new) for p in prompts]
    reqs = [Request(i, p, max_new) for i, p in enumerate(prompts)]
    for r in reqs:
        assert r.state == WAITING or r.state == "WAITING"
    srv = _serve(cfg, sparams, ctx, reqs, slots=2, num_pages=7, preempt=True)
    assert srv.stats["preemptions"] >= 1, srv.stats
    assert srv.stats["resumes"] == srv.stats["preemptions"], srv.stats
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, (i, got[i], w)
    assert all(r.state == RUNNING for r in srv.completed)  # resumed to done
    # prompt-only admission really co-ran them: some fused tick carried both
    assert any(len(t) > 1 for t in srv.pos_trace), srv.pos_trace
    # ... which the conservative reservation (no --preempt) cannot do on the
    # same pool: it serializes the two requests — the can_admit(reclaimable=)
    # fix is exactly the gap between these two schedules
    srv2 = _serve(cfg, sparams, ctx,
                  [Request(i, p, max_new) for i, p in enumerate(prompts)],
                  slots=2, num_pages=7)
    assert all(len(t) == 1 for t in srv2.pos_trace)
    assert {r.rid: r.out for r in srv2.completed} == got


def test_preempted_state_is_observable_midflight():
    """While the pool is dry the victim request is parked in state PREEMPTED
    with its swap slab recorded; pages come back only at resume."""
    cfg, _, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, size=(8,)).astype(np.int32)
               for _ in range(2)]
    reqs = [Request(i, p, 12) for i, p in enumerate(prompts)]
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, num_pages=7, preempt=True, ctx=ctx)
    for r in reqs:
        srv.submit(r)
    seen_preempted = False
    for _ in range(200):
        alive = srv.step()
        if any(r.state == PREEMPTED for r in reqs):
            seen_preempted = True
            victim = next(r for r in reqs if r.state == PREEMPTED)
            assert victim.rid in srv._swap
            assert victim in srv.preempted
        if not alive:
            break
    assert seen_preempted
    assert len(srv.completed) == 2


def test_prefix_share_throughput_on_shared_workload():
    """The capacity win that motivates the tentpole: on a shared-prefix
    workload over a constrained pool, --prefix-share admits all requests
    concurrently where the no-sharing baseline serializes waves — >= 1.5x
    admitted throughput (tokens per fused decode tick) at identical tokens."""
    cfg, _, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    rng = np.random.default_rng(31)
    common = rng.integers(0, cfg.vocab, size=(16,)).astype(np.int32)
    prompts = [np.concatenate(
        [common, rng.integers(0, cfg.vocab, size=(2,)).astype(np.int32)])
        for _ in range(4)]
    max_new = 6        # lifetime 18+6-1=23 tokens -> 6 pages/request

    def run(share):
        reqs = [Request(i, p, max_new) for i, p in enumerate(prompts)]
        srv = _serve(cfg, sparams, ctx, reqs, slots=4, num_pages=13,
                     prefix_share=share)
        toks = sum(len(r.out) for r in srv.completed)
        return srv, toks / max(len(srv.pos_trace), 1)

    base_srv, base_tpt = run(False)
    share_srv, share_tpt = run(True)
    # identical greedy tokens either way — sharing is a pure capacity win
    assert ({r.rid: r.out for r in share_srv.completed}
            == {r.rid: r.out for r in base_srv.completed})
    assert share_srv.stats["shared_pages"] >= 12, share_srv.stats  # 4 pages x 3
    ratio = share_tpt / base_tpt
    assert ratio >= 1.5, (ratio, base_tpt, share_tpt)
    # and it really was concurrency: all four slots decoded in one tick
    assert max(len(t) for t in share_srv.pos_trace) == 4
    assert max(len(t) for t in base_srv.pos_trace) <= 2


def test_submit_accepts_exact_fit_pool_with_sharing():
    """--prefix-share must not shrink the servable envelope: a request whose
    lifetime needs exactly the whole pool is accepted and served (a solo run
    can never need a CoW fork — refcount > 1 requires a live co-owner slot —
    so there is no hidden +1 page)."""
    cfg, sp, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    rng = np.random.default_rng(37)
    prompt = rng.integers(0, cfg.vocab, size=(8,)).astype(np.int32)
    max_new = 9                      # 8 + 9 - 1 = 16 tokens -> all 4 pages
    want = _reference(cfg, sp, sparams, ctx, prompt, max_new)
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, num_pages=5, prefix_share=True,
                 preempt=True, ctx=ctx)
    srv.submit(Request(0, prompt, max_new))   # must not raise
    srv.run()
    assert srv.completed[0].out == want
    assert srv.pt.free_pages == srv.pt.usable_pages


def test_windowed_scanned_arch_swaps_rings_and_mid_leaves_exact():
    """Mixed local/attn arch with a scanned mid-stack (gemma reduced,
    window=8): preemption must swap window RING slabs and recurrent per-slot
    rows alongside the paged pool, and the scanned `mid` cache leaves carry a
    leading (n_periods,) dim through CoW copy / swap gather / swap scatter —
    the llama-reduced oracles (2 unrolled layers) never touch that branch.
    Token-exact vs the sequential reference through ring wraparound, with
    prefix sharing on the attn layers' pages."""
    cfg = dataclasses.replace(get_config("gemma3-4b").reduced(),
                              policy="ternary", window=8)
    sp = transformer.build_specs(cfg)
    assert sp.n_periods >= 1          # the scanned mid-stack really exists
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    sparams = transformer.pack_for_serve(params, cfg)
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    rng = np.random.default_rng(29)
    common = rng.integers(0, cfg.vocab, size=(8,)).astype(np.int32)
    prompts = [np.concatenate(
        [common, rng.integers(0, cfg.vocab, size=(2,)).astype(np.int32)])
        for _ in range(2)]
    max_new = 10     # decode crosses the window=8 ring boundary
    want = [_reference(cfg, sp, sparams, ctx, p, max_new) for p in prompts]
    reqs = [Request(i, p, max_new) for i, p in enumerate(prompts)]
    # 7 usable pages vs 5-page lifetimes: dries mid-decode -> swap
    srv = _serve(cfg, sparams, ctx, reqs, slots=2, num_pages=8,
                 prefix_share=True, preempt=True)
    assert srv.stats["preemptions"] >= 1, srv.stats
    assert srv.stats["shared_pages"] >= 1, srv.stats
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, (i, got[i], w)


def test_fifo_priority_and_explicit_priority_classes():
    """The victim rule: preemption evicts the lowest-priority running request
    (priority class first, youngest rid within a class), so a high-priority
    latecomer can claim pages from a low-priority incumbent and still every
    request completes token-exactly."""
    cfg, sp, sparams = _built("ternary")
    ctx = ModelCtx(mode="serve", dtype=jnp.float32)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab, size=(8,)).astype(np.int32)
               for _ in range(3)]
    max_new = 10
    want = [_reference(cfg, sp, sparams, ctx, p, max_new) for p in prompts]
    # rid 2 outranks the incumbents
    reqs = [Request(0, prompts[0], max_new),
            Request(1, prompts[1], max_new),
            Request(2, prompts[2], max_new, priority=1)]
    srv = _serve(cfg, sparams, ctx, reqs, slots=3, num_pages=8, preempt=True)
    got = {r.rid: r.out for r in srv.completed}
    for i, w in enumerate(want):
        assert got[i] == w, (i, got[i], w)
    assert srv.stats["preemptions"] >= 1, srv.stats


SCRIPT_TP = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 8
from repro.configs import get_config
from repro.launch.serve import Request, Server
from repro.models import transformer
from repro.models.common import ModelCtx

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
CACHE_LEN, PAGE_SIZE = 32, 4
# 10 total pages (incl. scratch page 0): even, so the pool's page axis
# divides data=2 and really device-shards (an odd pool falls back to
# replicated) — and tight enough that decode growth dries the pool and
# forces preemption + swap against the sharded pool.
# slots=2 divides data=2: a decode batch the data axis does NOT divide
# miscompiles on the CPU SPMD partitioner (seed-reproducible with the plain
# paged server at slots=3 — same landmine family as the head-axis
# with_sharding_constraint note in models/common.py; see docs/SERVING.md).
NUM_PAGES = 10

cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(), policy="ternary")
params = transformer.init(jax.random.PRNGKey(0), cfg)
sparams = transformer.pack_for_serve(params, cfg)
rng = np.random.default_rng(41)
common = rng.integers(0, cfg.vocab, size=(8,)).astype(np.int32)
prompts = [np.concatenate([common,
                           rng.integers(0, cfg.vocab, size=(2,)).astype(np.int32)])
           for _ in range(3)]
# exact duplicate FIRST: r0/r1 co-run as sharers of the partial boundary
# page, so the first decode tick must CoW-fork against the sharded pool
prompts.insert(1, prompts[0].copy())

# Greedy on purpose: the TP exactness contract is token-level (argmax) —
# cross-shard float reduction layouts differ in low bits, so sampled draws
# may flip under a mesh. CoW still fires (the duplicate prompt aliases the
# boundary page and forks on its first decode write); the sampled-divergence
# CoW oracle runs single-device in test_cow_isolates_sampled_divergence.
def serve(mesh_):
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, num_pages=NUM_PAGES, ctx=ctx, mesh=mesh_,
                 prefix_share=True, preempt=True)
    if mesh_ is not None:
        assert srv.cache["first"]["k"].sharding.spec[0] == "data"
        assert isinstance(srv.pt.table, np.ndarray)      # host-global
    for i, p in enumerate(prompts):
        srv.submit(Request(i, p, 14))
    srv.run()
    assert len(srv.completed) == len(prompts)
    assert srv.compile_counts["decode"] == 1, srv.compile_counts
    assert srv.stats["shared_pages"] > 0, srv.stats
    assert srv.pt.free_pages == srv.pt.usable_pages
    return srv

ctx = ModelCtx(mode="serve", dtype=jnp.float32)
single = serve(None)
want = {r.rid: r.out for r in single.completed}
tp = serve(mesh)
got = {r.rid: r.out for r in tp.completed}
assert got == want, ("TP sched serve diverged", got, want)
# the host-side scheduler made identical decisions on both (greedy tokens
# equal => same admission/fork/preempt trace), and the CoW + swap paths
# really ran against the data-sharded pool
assert tp.stats == single.stats, (tp.stats, single.stats)
assert tp.stats["cow_forks"] >= 1, tp.stats
assert tp.stats["preemptions"] >= 1, tp.stats
print("stats:", tp.stats)
print("SCHED_TP_OK")
'''


def test_mesh_share_preempt_token_exact_vs_single_device():
    """Forced-8-device (data=2, model=4) mesh: --prefix-share --preempt
    serving — CoW forks and swap in/out against the data-sharded pool —
    stays token-exact (greedy) vs the single-device scheduler, with an
    identical host-side scheduling trace. Subprocess so the device-count
    flag can't leak into the suite (same pattern as tests/test_serving_tp.py)."""
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    r = subprocess.run([sys.executable, "-c", SCRIPT_TP],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "SCHED_TP_OK" in r.stdout, r.stdout[-2000:]
