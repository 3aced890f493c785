"""Serving-layer bench: KV layouts and scheduler policies under arrival
traffic (docs/SERVING.md).

    PYTHONPATH=src python benchmarks/serve_bench.py [--arch llama3.2-3b]
                                                    [--json BENCH_serve.json]
                                                    [--scenario poisson]

Scenarios:
  mixed         paged vs contiguous layout on mixed-length traffic — the
                paged win is *capacity* (the slab reserves slots*cache_len
                tokens up front, the pool holds only live coverage)
  shared-prefix identical 16-token prompt prefixes over a constrained pool,
                --prefix-share off vs on — the sharing win is *admitted
                throughput* (tokens per fused decode tick): aliased pages
                let every request co-run where the baseline serializes waves
  oversubscribed a pool smaller than the aggregate decode lifetime,
                conservative reservation vs --preempt — preemption converts
                reserved-but-idle headroom into live decode slots, at the
                cost of swap traffic (counted)
  spec          self-speculative decoding on the w4a8 policy: the SAME
                plane-stacked weights serve as their own draft model
                (--spec-draft planes:1), sequential decode vs a K-token
                propose/verify tick. Run on draft-friendly weights — every
                code floor-snapped to its top plane so the truncated-plane
                draft composes the full-precision value exactly — the
                accept rate approaches 100% and the win is *tokens per
                verify tick* (spec_tokens_per_tick_speedup headline;
                acceptance floor 1.3x). On un-snapped random weights a
                1-plane draft accepts ~nothing (measured 0%): accept rate
                is a property of how much of the weight's energy the top
                planes carry, which real quantized checkpoints — unlike
                random init — concentrate there
  poisson       OPEN-LOOP arrival process: Poisson arrivals of a long/short
                prompt mix (default 25% long at 0.75*cache_len), whole-prompt
                prefill vs --chunk-tokens. Reports wall-clock p50/p99 TTFT
                (scheduled arrival -> first token) and inter-token latency
                per request. The chunked win is the *latency tail*: a long
                prompt's prefill no longer freezes every in-flight decode
                slot for a whole jitted prefill call, so the p99 TTFT of the
                short requests queued behind it collapses
                (poisson_p99_ttft_speedup headline; acceptance floor 2x).
                Arrival times are calibrated once against the baseline's
                measured tick time and REUSED for the chunked run, so both
                configs face the identical offered load; jit compile time is
                excluded by a warmup workload that touches every signature
                before the clock starts

  moe           MoE routing telemetry on deepseek-moe: identical traffic at
                router-capacity headroom vs a drop-forcing capacity_factor,
                reporting per-expert utilization and the drop rate straight
                from Server.stats (docs/MOE.md) — the columns the EP serving
                deployment monitors
  multi-tenant  two tenants (pure-attn + windowed arch, different precision
                policies) co-scheduled on ONE shared page pool with prefix
                sharing, preemption and the tiered (device→host→disk)
                prefix cache, then a cold-restart pass over the same slab
                directory — the reuse win is *prefix pages promoted from
                the tier instead of re-prefilled* (per-tenant p50/p99
                TTFT/ITL from the SLO counters ride along)

A further micro-scenario, `decode-attn`, drops below the scheduler and times
the decode attention READ path itself at a fixed provisioned page-table
width while the active length sweeps 128→4096: the jitted server's gather
path always materializes (and dequantizes) the full table width per step,
the fused page-walk kernel (kernels/paged_attn) stops at the slot's last
active page. Interpret-mode wall time is NOT TPU performance; the modeled
per-step HBM KV traffic column is the layout-level metric, wall time is
reported alongside for the CPU lane.

Reports tok/s and tok/tick per row, jit signature counts (the bucketing +
fixed-decode + CoW discipline), page/pool utilization, and scheduler stats;
`--json` writes the whole table plus the headline ratios for the CI bench
lane (BENCH_serve.json artifact).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.serve import Request, Server
from repro.models import transformer
from repro.models.common import ModelCtx


def _mixed_traffic(cfg, n, rng):
    return [Request(i, rng.integers(0, cfg.vocab,
                                    size=(int(rng.integers(2, 25)),)).astype(np.int32),
                    int(rng.integers(4, 13)))
            for i in range(n)]


def _shared_traffic(cfg, n, rng, prefix_len=16, tail=2, max_new=6):
    common = rng.integers(0, cfg.vocab, size=(prefix_len,)).astype(np.int32)
    return [Request(i, np.concatenate(
        [common, rng.integers(0, cfg.vocab, size=(tail,)).astype(np.int32)]),
        max_new) for i in range(n)]


def _run_one(cfg, sparams, reqs, *, label, scenario, **kw):
    srv = Server(cfg, sparams, ctx=ModelCtx(mode="serve"), **kw)
    for r in reqs:
        srv.submit(r)
    t0 = time.perf_counter()
    ticks = srv.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out) for r in srv.completed)
    row = dict(
        scenario=scenario, config=label,
        tok_s=toks / dt, tok_per_tick=toks / max(ticks, 1), ticks=ticks,
        jit_prefill=srv.compile_counts["prefill"],
        jit_decode=srv.compile_counts["decode"],
        jit_cow=srv.compile_counts["cow"],
    )
    if srv.paged:
        # peak_pages is measured at the pool (shared pages count once) —
        # with sharing on it can be far below the per-slot coverage sum
        row.update(kv_reserved_tokens=srv.pt.usable_pages * srv.page_size,
                   kv_peak_live_pages=srv.stats["peak_pages"],
                   **{k: v for k, v in srv.stats.items() if k != "peak_pages"})
    else:
        row.update(kv_reserved_tokens=srv.slots * srv.cache_len,
                   kv_peak_live_pages="-",
                   **{k: v for k, v in srv.stats.items() if k != "peak_pages"})
    return row


def run(arch="llama3.2-3b", requests=12, slots=4, cache_len=128, page_size=16):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(cfg, policy="ternary")
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    sparams = transformer.pack_for_serve(params, cfg)
    rows = []

    # -- mixed-length traffic: paged vs contiguous (identical traffic) -------
    for paged in (True, False):
        rows.append(_run_one(
            cfg, sparams, _mixed_traffic(cfg, requests, np.random.default_rng(0)),
            label="paged" if paged else "contiguous", scenario="mixed",
            slots=slots, cache_len=cache_len, paged=paged, page_size=page_size))

    # -- shared-prefix workload over a constrained pool: sharing off vs on ---
    # geometry mirrors tests/test_serving_sched.py::test_prefix_share_
    # throughput...: 4 requests x (16 shared + 2 private) tokens, 6 new each;
    # 12 usable pages of 4 fit all four concurrently ONLY when the common
    # prefix aliases
    sh_kw = dict(slots=4, cache_len=32, paged=True, page_size=4, num_pages=13)
    for share in (False, True):
        rows.append(_run_one(
            cfg, sparams, _shared_traffic(cfg, 4, np.random.default_rng(1)),
            label="share-on" if share else "share-off",
            scenario="shared-prefix", prefix_share=share, **sh_kw))

    # -- oversubscribed pool: conservative reservation vs preempt+swap -------
    ov_rng = np.random.default_rng(2)
    ov_prompts = [ov_rng.integers(0, cfg.vocab, size=(8,)).astype(np.int32)
                  for _ in range(3)]
    ov_kw = dict(slots=3, cache_len=32, paged=True, page_size=4, num_pages=9)
    for preempt in (False, True):
        reqs = [Request(i, p, 12) for i, p in enumerate(ov_prompts)]
        rows.append(_run_one(
            cfg, sparams, reqs,
            label="preempt" if preempt else "reserve",
            scenario="oversubscribed", preempt=preempt, **ov_kw))
    return rows


def _snap_low_planes(sparams, keep=1):
    """Draft-friendly weights: floor-snap every plane-stacked weight's codes
    to their top `keep` plane(s), regenerating BOTH the plane stack and the
    direct twin from the snapped codes (scales untouched), so the serving
    comparison stays apples-to-apples — sequential and speculative runs see
    the identical model, and the truncated-plane draft composes exactly the
    values the full cell reads."""
    from repro.core import pack

    def walk(t):
        if not isinstance(t, dict):
            return t
        t = {k: walk(v) for k, v in t.items()}
        planes = t.get("w_planes")
        if planes is None:
            return t
        bits = planes.shape[-3]
        k = planes.shape[-1] * pack.WORD
        codes = np.asarray(pack.unpack_planes_i8(planes, k, bits))
        sh = bits - min(keep, bits)
        codes = ((codes >> sh) << sh).astype(np.int8)   # arithmetic: floor
        t["w_planes"] = pack.pack_planes(codes, bits)
        if "w_q4" in t:                    # int4 twin: (out, in) nibbles
            t["w_q4"] = pack.pack_int4(codes)
        elif "w_q" in t:                   # int8 twin: (in, out) codes
            t["w_q"] = jax.numpy.asarray(np.swapaxes(codes, -1, -2))
        return t

    return walk(sparams)


def spec_rows(arch="llama3.2-3b", *, requests=6, slots=2, cache_len=64,
              page_size=8, max_new=16, spec_k=4):
    """The `spec` scenario: identical requests and identical (snapped)
    weights, sequential decode vs self-speculative propose/verify."""
    cfg = dataclasses.replace(get_config(arch).reduced(), policy="w4a8")
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    sparams = _snap_low_planes(
        transformer.pack_for_serve(params, cfg, plane_twins=True))
    kw = dict(slots=slots, cache_len=cache_len, paged=True,
              page_size=page_size)
    rows = []
    for label, skw in (("sequential", {}),
                       ("speculative",
                        dict(spec_draft="planes:1", spec_k=spec_k))):
        rng = np.random.default_rng(4)      # identical traffic both arms
        reqs = [Request(i, rng.integers(0, cfg.vocab, size=(int(rng.integers(
            4, 17)),)).astype(np.int32), max_new) for i in range(requests)]
        rows.append(_run_one(cfg, sparams, reqs, label=label, scenario="spec",
                             **kw, **skw))
    return rows


def moe_rows(arch="deepseek-moe-16b", *, requests=6, slots=2, cache_len=64,
             page_size=8):
    """The `moe` scenario: identical mixed-length traffic through an MoE
    arch at two router capacities — the reduced default (capacity_factor=8,
    headroom for every top-k assignment) vs a deliberately tight 0.5 that
    forces slot-overflow drops. The routing telemetry the server accumulates
    (Server.stats: moe_routed / moe_dropped / moe_expert_tokens, see
    docs/MOE.md §Stats) surfaces as per-row columns: `moe_drop_rate` is
    dropped/routed, `moe_expert_util` each expert's share of the kept
    assignments. Single-process rows — EP changes the *placement* of this
    exact computation, not the counters (tests/test_moe_serving.py holds the
    stats shard-count-invariant), so the utilization/drop columns here stand
    for the sharded deployment too."""
    rows = []
    for label, cap in (("capacity-headroom", None), ("capacity-tight", 0.5)):
        cfg = get_config(arch).reduced()
        if cap is not None:
            cfg = dataclasses.replace(cfg, capacity_factor=cap)
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        sparams = transformer.pack_for_serve(params, cfg)
        row = _run_one(
            cfg, sparams, _mixed_traffic(cfg, requests, np.random.default_rng(5)),
            label=label, scenario="moe", slots=slots, cache_len=cache_len,
            paged=True, page_size=page_size)
        et = row.pop("moe_expert_tokens")
        kept = sum(et)
        row["capacity_factor"] = cfg.capacity_factor
        row["moe_drop_rate"] = row["moe_dropped"] / max(row["moe_routed"], 1)
        row["moe_expert_util"] = "|".join(
            f"{v / max(kept, 1):.3f}" for v in et)
        rows.append(row)
    return rows


def decode_attn_rows(active_lens=(128, 512, 1024, 2048, 4096), *, slots=4,
                     page_size=64, table_pages=128, hk=2, hq=4, dh=32,
                     reps=20):
    """`decode-attn` micro-scenario: per-step attention read-path time at a
    FIXED provisioned table width (table_pages * page_size = 8192 tokens),
    active length swept. Three variants per length:

      gather-full     what the jitted server's gather path pays every step
                      (pos is a tracer -> the full fixed-signature width is
                      gathered + dequantized)
      gather-bounded  the eager length-bound (attn_decode slices the table
                      to max(pos)//P + 1 columns) — oracle/bench callers
      fused           kernels.paged_attn.paged_flash_decode — the page walk
                      early-stops at each slot's last active page

    int8 pool so the gather's full-width dequantize cost is visible.
    `hbm_kv_bytes_per_step` models the pool operand traffic each variant
    actually touches (the TPU-relevant metric; wall time here is
    interpret-mode CPU)."""
    import jax.numpy as jnp

    from repro.kernels import paged_attn as pa
    from repro.models.attention import KV_SCALE

    num_pages = 1 + slots * table_pages
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (slots, hq, dh), jnp.float32)
    kp = jax.random.randint(kk, (num_pages, page_size, hk, dh), -127, 128,
                            jnp.int8)
    vp = jax.random.randint(kv, (num_pages, page_size, hk, dh), -127, 128,
                            jnp.int8)
    pages = np.stack([1 + r * table_pages + np.arange(table_pages)
                      for r in range(slots)]).astype(np.int32)
    pages = jnp.asarray(pages)

    @jax.jit
    def gather(pages_, pos_):
        s = pages_.shape[1] * page_size
        kf = kp[pages_].reshape(slots, s, hk, dh).astype(jnp.float32) * KV_SCALE
        vf = vp[pages_].reshape(slots, s, hk, dh).astype(jnp.float32) * KV_SCALE
        valid = jnp.arange(s)[None, :] <= pos_[:, None]
        qg = q.reshape(slots, hk, hq // hk, dh)
        sc = jnp.einsum("bhgd,bshd->bhgs", qg, kf) / dh ** 0.5
        sc = jnp.where(valid[:, None, None, :], sc, -1e30)
        a = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhgs,bshd->bhgd", a, vf)

    def time_us(fn):
        jax.block_until_ready(fn())                      # compile outside
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn())
        return (time.perf_counter() - t0) / reps * 1e6

    bkp = pa.resolve_pages_per_block()
    kv_bytes = 1                                          # int8 pool
    rows = []
    for al in active_lens:
        pos = jnp.full((slots,), al - 1, jnp.int32)
        act_pages = (al - 1) // page_size + 1
        full_b = slots * 2 * table_pages * page_size * hk * dh * kv_bytes
        act_b = slots * 2 * act_pages * page_size * hk * dh * kv_bytes
        for name, fn, bb in (
            ("gather-full", lambda: gather(pages, pos), full_b),
            ("gather-bounded",
             lambda: gather(pages[:, :act_pages], pos), act_b),
            ("fused", lambda: pa.paged_flash_decode(
                q, kp, vp, pages, pos, pages_per_block=bkp,
                kv_scale=KV_SCALE), act_b),
        ):
            rows.append(dict(scenario="decode-attn", config=name,
                             active_len=al, us_per_step=time_us(fn),
                             hbm_kv_bytes_per_step=bb,
                             pages_per_block=bkp if name == "fused" else "-"))
    return rows


def multi_tenant_rows(*, requests=3, max_new=6, cache_len=32, page_size=4,
                      tier_dir=None):
    """The `multi-tenant` scenario: two tenants (pure-attn llama + windowed
    gemma, different precision policies) co-scheduled on ONE shared page
    pool with prefix sharing, cross-tenant preemption, and the tiered
    prefix cache — then a COLD-RESTART pass: a fresh MultiServer over the
    same disk-slab directory serving identical traffic, measuring how many
    prefixes it re-admits from the tier instead of re-prefilling
    (`tier_hits`, `prefill_skips`). Per-tenant p50/p99 TTFT/ITL come from
    the scheduler's SLO counters (ticks are the interpret-mode-stable
    metric; wall seconds ride along). Pool occupancy is PageTable.stats()'s
    live/usable fraction — page 0 scratch is not demand."""
    import tempfile
    import zlib

    from repro.launch.cache_tiers import PageStore
    from repro.launch.multi_serve import MultiServer, TenantSpec

    tier_dir = tier_dir or tempfile.mkdtemp(prefix="serve-bench-tier-")
    tenants = [
        TenantSpec(model_id="llama#0", arch="llama3.2-3b", policy="ternary",
                   slots=2, cache_len=cache_len, weight=2, priority=1,
                   reduced=True),
        TenantSpec(model_id="gemma#1", arch="gemma3-4b", policy="w-ternary",
                   slots=2, cache_len=cache_len, weight=1, priority=0,
                   reduced=True),
    ]

    def traffic(t, vocab):
        # page-aligned common prefix, stable per tenant AND across phases,
        # so the share index aliases within a phase and the restart pass
        # probes the exact disk-tier keys the cold pass flushed
        prng = np.random.default_rng(zlib.crc32(t.model_id.encode()))
        head = prng.integers(0, vocab, size=(page_size,))
        tails = np.random.default_rng(1)
        return [np.concatenate(
            [head, tails.integers(0, vocab, size=(3 + 2 * i,))]
        ).astype(np.int32) for i in range(requests)]

    rows = []
    for phase in ("cold", "restart"):
        ms = MultiServer(tenants, page_size=page_size, prefix_share=True,
                         preempt=True,
                         tier=PageStore(host_capacity=16, disk_dir=tier_dir))
        for t in tenants:
            for p in traffic(t, ms.servers[t.model_id].cfg.vocab):
                ms.submit(t.model_id, p, max_new)
        t0 = time.perf_counter()
        ticks = ms.run()
        dt = time.perf_counter() - t0
        ms.flush_tier()
        stt = ms.stats()
        for t in tenants:
            r = stt[t.model_id]
            toks = sum(len(q.out)
                       for q in ms.servers[t.model_id].completed)
            rows.append(dict(
                scenario="multi-tenant", config=f"{phase}:{t.model_id}",
                completed=r["completed"], tok_s=toks / dt,
                tok_per_tick=toks / max(ticks, 1),
                ttft_p50_ticks=r["ttft_ticks_p50"],
                ttft_p99_ticks=r["ttft_ticks_p99"],
                itl_p50_ticks=r["itl_ticks_p50"],
                itl_p99_ticks=r["itl_ticks_p99"],
                ttft_p50_s=r["ttft_s_p50"], ttft_p99_s=r["ttft_s_p99"],
                itl_p50_s=r["itl_s_p50"], itl_p99_s=r["itl_s_p99"],
                shared_pages=r["shared_pages"],
                preemptions=r["preemptions"],
                tier_hits=(r["tier_hits_device"] + r["tier_hits_host"]
                           + r["tier_hits_disk"]),
                tier_hits_promoted=(r["tier_hits_host"]
                                    + r["tier_hits_disk"]),
                prefill_skips=r["prefill_skips"],
                jit_signatures=r["jit_signatures"],
                pool_occupancy_exit=stt["pool"]["occupancy"],
            ))
    return rows


def _poisson_traffic(cfg, n, rng, cache_len, max_new, long_frac=0.25):
    """Open-loop arrival schedule: (arrival_gap_units, Request) with unit-mean
    exponential inter-arrival gaps (scaled to seconds by the caller) and a
    long/short prompt mix — long prompts are 0.75*cache_len, the tail that
    whole-prompt prefill turns into a decode freeze."""
    t, out = 0.0, []
    for i in range(n):
        t += float(rng.exponential(1.0))
        plen = ((3 * cache_len) // 4 if rng.random() < long_frac
                else int(rng.integers(4, 17)))
        out.append((t, Request(
            i, rng.integers(0, cfg.vocab, size=(plen,)).astype(np.int32),
            max_new)))
    return out


def _pct(vals, q):
    return float(np.percentile(np.asarray(vals, np.float64), q)) if vals else 0.0


def _run_arrivals(cfg, sparams, traffic, *, label, chunk_tokens, gap_s=None,
                  **kw):
    """Serve an open-loop arrival schedule; returns (row, gap_s).

    `gap_s` scales the unit-mean arrival gaps to seconds. None = calibrate
    from this run's warmup tick time (the baseline does this; the chunked run
    reuses the same value so both face the identical offered load). TTFT
    counts from the SCHEDULED arrival, not the actual submit — when the loop
    is stuck inside a long prefill, that queueing delay is the metric."""
    srv = Server(cfg, sparams, ctx=ModelCtx(mode="serve"),
                 chunk_tokens=chunk_tokens, **kw)
    # warmup on the same instance: touch every signature the measured run
    # will hit (short + long prefill buckets or the chunk step, decode), so
    # no jit compile lands inside a timed tick
    wrng = np.random.default_rng(99)
    # one warmup request per prefill bucket the traffic actually hits (the
    # chunked arm has no buckets — its requests warm the chunk + decode
    # signatures instead); a missed bucket would drop a multi-second jit
    # compile into the middle of the timed loop and corrupt the TTFT tail
    if srv.chunk_tokens:
        warm_lens = (4, (3 * srv.cache_len) // 4)
    else:
        by_bucket: dict = {}
        for _, req in traffic:
            b = srv._bucket(len(req.prompt))
            by_bucket[b] = max(by_bucket.get(b, 0), len(req.prompt))
        warm_lens = sorted(by_bucket.values())
    for j, plen in enumerate(warm_lens):
        srv.submit(Request(10_000 + j,
                           wrng.integers(0, cfg.vocab, size=(plen,))
                           .astype(np.int32), 2))
    srv.run()
    if gap_s is None:
        # calibrate on a SECOND, hot warmup pass: the first run's wall time
        # is dominated by jit compiles, which would inflate the arrival gaps
        # by orders of magnitude and turn the open loop into an idle crawl.
        # mean inter-arrival = 2 hot ticks: with slots*max_new decode ticks
        # of work per request this offers near-saturation load, where the
        # latency tail actually separates the two prefill policies
        for j in range(2):
            srv.submit(Request(20_000 + j,
                               wrng.integers(0, cfg.vocab, size=(6,))
                               .astype(np.int32), 4))
        wt0 = time.perf_counter()
        wticks = srv.run()
        gap_s = 2.0 * (time.perf_counter() - wt0) / max(wticks, 1)
    srv.completed.clear()

    arr = [(g * gap_s, req) for g, req in traffic]
    n = len(arr)
    submit_t, first_t, done_t = {}, {}, {}
    reqs = {req.rid: req for _, req in arr}
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < n and arr[i][0] <= now:
            ts, req = arr[i]
            srv.submit(req)
            submit_t[req.rid] = ts
            i += 1
        busy = (srv.queue or srv.preempted
                or any(r is not None for r in srv.slot_req))
        if not busy:
            if i >= n:
                break
            time.sleep(max(0.0, arr[i][0] - (time.perf_counter() - t0)))
            continue
        srv.step()
        now = time.perf_counter() - t0
        for rid, req in reqs.items():
            if rid not in submit_t:
                continue
            if req.out and rid not in first_t:
                first_t[rid] = now
            if req.done and rid not in done_t:
                done_t[rid] = now
    ttft = [first_t[r] - submit_t[r] for r in first_t]
    itl = [(done_t[r] - first_t[r]) / (len(reqs[r].out) - 1)
           for r in done_t if len(reqs[r].out) > 1]
    toks = sum(len(r.out) for r in reqs.values())
    span = max(done_t.values()) if done_t else 1.0
    row = dict(
        scenario="poisson", config=label,
        ttft_p50_s=_pct(ttft, 50), ttft_p99_s=_pct(ttft, 99),
        itl_p50_s=_pct(itl, 50), itl_p99_s=_pct(itl, 99),
        tok_s=toks / span, requests=n,
        mean_interarrival_s=gap_s,
        jit_total=sum(srv.compile_counts.values()),
        chunk_ticks=srv.stats["chunk_ticks"],
        plan_hits=srv.stats["plan_hits"], fences=srv.stats["fences"],
    )
    return row, gap_s, srv


def poisson_rows(cfg, sparams, *, requests=24, slots=4, cache_len=128,
                 page_size=16, max_new=8, chunk_tokens=16):
    """The arrival-process scenario: identical Poisson schedule, whole-prompt
    prefill vs chunked prefill fused into the decode tick."""
    kw = dict(slots=slots, cache_len=cache_len, paged=True,
              page_size=page_size)
    rows, gap, servers = [], None, []
    for label, ct in (("whole-prompt", 0), ("chunked", chunk_tokens)):
        traffic = _poisson_traffic(cfg, requests, np.random.default_rng(3),
                                   cache_len, max_new)
        row, gap, srv = _run_arrivals(cfg, sparams, traffic, label=label,
                                      chunk_tokens=ct, gap_s=gap, **kw)
        rows.append(row)
        servers.append(srv)
    return rows, servers


def _ratio(rows, scenario, a, b, key="tok_per_tick"):
    sel = {r["config"]: r[key] for r in rows if r["scenario"] == scenario}
    return sel[a] / sel[b]


def _print_rows(rows, header):
    print(header)
    keys = list(rows[0])
    print(",".join(keys))
    for r in rows:
        print(",".join(f"{r[k]:.4f}" if isinstance(r[k], float) else str(r[k])
                       for k in keys))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--scenario", default="all",
                    choices=("all", "scheduler", "decode-attn", "poisson",
                             "spec", "multi-tenant", "moe"),
                    help="'scheduler' = the mixed/shared-prefix/"
                         "oversubscribed trio; 'poisson' = the open-loop "
                         "arrival-process scenario only (the CI serving-lane "
                         "smoke); 'spec' = self-speculative decoding on "
                         "draft-friendly snapped w4a8 weights; "
                         "'multi-tenant' = two archs x two policies on one "
                         "shared pool + tiered cache, with a cold-restart "
                         "prefix-reuse pass; 'moe' = MoE routing telemetry "
                         "(expert utilization + drop rate) at headroom vs "
                         "drop-forcing router capacity")
    ap.add_argument("--tier-dir", default=None,
                    help="disk-slab directory for the multi-tenant "
                         "scenario's tiered cache (default: a temp dir)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="tokens proposed per tick in the spec scenario")
    ap.add_argument("--poisson-requests", type=int, default=24)
    ap.add_argument("--chunk-tokens", type=int, default=16,
                    help="chunk size for the poisson scenario's chunked arm")
    ap.add_argument("--jit-budget", type=int, default=None,
                    help="fail (exit 1) if any poisson-scenario server "
                         "traced more total jit signatures than this — the "
                         "CI recompile-regression gate for the arrival "
                         "smoke")
    ap.add_argument("--json", default=None, metavar="OUT_JSON",
                    help="write rows + headline ratios (BENCH_serve.json "
                         "artifact for the CI bench lane)")
    args = ap.parse_args(argv)
    out = {}
    all_rows = []

    if args.scenario in ("all", "scheduler"):
        rows = run(args.arch, args.requests, args.slots, args.cache_len,
                   args.page_size)
        _print_rows(rows, "# serve bench (identical traffic within each "
                          "scenario)")
        share_x = _ratio(rows, "shared-prefix", "share-on", "share-off")
        preempt_x = _ratio(rows, "oversubscribed", "preempt", "reserve")
        print(f"# shared-prefix admitted-throughput: {share_x:.2f}x with "
              f"--prefix-share (acceptance floor 1.5x)")
        print(f"# oversubscribed admitted-throughput: {preempt_x:.2f}x with "
              f"--preempt")
        out.update(rows=rows, shared_prefix_speedup_tok_per_tick=share_x,
                   preempt_speedup_tok_per_tick=preempt_x)
        all_rows += rows

    if args.scenario in ("all", "poisson"):
        cfg = dataclasses.replace(get_config(args.arch).reduced(),
                                  policy="ternary")
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        sparams = transformer.pack_for_serve(params, cfg)
        prows, servers = poisson_rows(
            cfg, sparams, requests=args.poisson_requests, slots=args.slots,
            cache_len=args.cache_len, page_size=args.page_size,
            chunk_tokens=args.chunk_tokens)
        _print_rows(prows, "# poisson arrival scenario (open loop, identical "
                           "schedule; wall-clock seconds)")
        sel = {r["config"]: r for r in prows}
        ttft_x = (sel["whole-prompt"]["ttft_p99_s"]
                  / max(sel["chunked"]["ttft_p99_s"], 1e-9))
        print(f"# poisson p99 TTFT: {ttft_x:.2f}x better with chunked "
              f"prefill (acceptance floor 2x)")
        out.update(poisson_rows=prows, poisson_p99_ttft_speedup=ttft_x)
        all_rows += prows
        if args.jit_budget is not None:
            for r in prows:
                if r["jit_total"] > args.jit_budget:
                    raise SystemExit(
                        f"jit budget exceeded in poisson scenario "
                        f"({r['config']}): {r['jit_total']} signatures > "
                        f"committed budget {args.jit_budget}")

    if args.scenario in ("all", "spec"):
        srows = spec_rows(args.arch, spec_k=args.spec_k)
        _print_rows(srows, "# spec scenario (self-speculative decoding, "
                           "draft-friendly snapped w4a8 weights, identical "
                           "traffic)")
        spec_x = _ratio(srows, "spec", "speculative", "sequential")
        sp = next(r for r in srows if r["config"] == "speculative")
        acc_rate = sp["spec_accepted"] / max(sp["spec_proposed"], 1)
        print(f"# spec decode: {spec_x:.2f}x tokens/tick with --spec-draft "
              f"planes:1 --spec-k {args.spec_k}, accept-rate "
              f"{acc_rate:.0%} (acceptance floor 1.3x)")
        out.update(spec_rows=srows, spec_accept_rate=acc_rate,
                   spec_tokens_per_tick_speedup=spec_x)
        all_rows += srows

    if args.scenario in ("all", "multi-tenant"):
        mrows = multi_tenant_rows(tier_dir=args.tier_dir)
        _print_rows(mrows, "# multi-tenant scenario (2 archs x 2 policies, "
                           "one shared pool, tiered prefix cache; cold run "
                           "then cold-restart reuse)")
        restart = [r for r in mrows if r["config"].startswith("restart:")]
        reuse_hits = sum(r["tier_hits_promoted"] for r in restart)
        reuse_skips = sum(r["prefill_skips"] for r in restart)
        attn = {p: next(r for r in mrows
                        if r["config"] == f"{p}:llama#0")
                for p in ("cold", "restart")}
        ttft_x = (attn["cold"]["ttft_p50_ticks"]
                  / max(attn["restart"]["ttft_p50_ticks"], 1e-9))
        print(f"# multi-tenant restart reuse: {reuse_hits} prefix pages "
              f"promoted from host/disk, {reuse_skips} prefills skipped "
              f"outright; attn-tenant p50 TTFT {ttft_x:.2f}x vs cold "
              f"(acceptance floor: >= 1 page reused without re-prefill)")
        out.update(multi_tenant_rows=mrows,
                   multi_tenant_restart_tier_hits=reuse_hits,
                   multi_tenant_restart_prefill_skips=reuse_skips,
                   multi_tenant_restart_ttft_p50_speedup=ttft_x)
        all_rows += mrows

    if args.scenario in ("all", "moe"):
        qrows = moe_rows()
        _print_rows(qrows, "# moe scenario (identical traffic, router "
                           "capacity headroom vs drop-forcing; utilization "
                           "= share of kept top-k assignments per expert)")
        tight = next(r for r in qrows if r["config"] == "capacity-tight")
        head = next(r for r in qrows if r["config"] == "capacity-headroom")
        print(f"# moe routing: drop-rate {tight['moe_drop_rate']:.1%} at "
              f"capacity_factor={tight['capacity_factor']} vs "
              f"{head['moe_drop_rate']:.1%} at headroom; expert util "
              f"[{head['moe_expert_util']}] (acceptance: headroom arm "
              f"drops nothing, tight arm drops > 0)")
        out.update(moe_rows=qrows,
                   moe_tight_drop_rate=tight["moe_drop_rate"],
                   moe_headroom_drop_rate=head["moe_drop_rate"])
        all_rows += qrows

    if args.scenario in ("all", "decode-attn"):
        attn_rows = decode_attn_rows()
        _print_rows(attn_rows, "# decode-attn micro-scenario (per-step "
                               "attention read path; interpret-mode wall "
                               "time + modeled pool traffic)")

        def _attn(cfg_, al):
            return next(r for r in attn_rows
                        if r["config"] == cfg_ and r["active_len"] == al)
        fused_x_1024 = (_attn("gather-full", 1024)["us_per_step"]
                        / _attn("fused", 1024)["us_per_step"])
        fused_bytes_x_1024 = (
            _attn("gather-full", 1024)["hbm_kv_bytes_per_step"]
            / _attn("fused", 1024)["hbm_kv_bytes_per_step"])
        print(f"# decode-attn @1024 active: fused {fused_x_1024:.2f}x faster "
              f"than the jitted gather (full width), {fused_bytes_x_1024:.2f}x "
              f"less pool traffic")
        out.update(decode_attn_rows=attn_rows,
                   decode_attn_fused_speedup_at_1024=fused_x_1024,
                   decode_attn_fused_bytes_ratio_at_1024=fused_bytes_x_1024)
        all_rows += attn_rows

    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, default=str)
        print(f"# wrote {args.json}")
    return all_rows


if __name__ == "__main__":
    main()
