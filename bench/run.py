"""The serving benchmark: one cell of BENCHMARK.json on the chip(s) JAX finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Makes the weights on the device from the seed (the program's
`serve_params`), builds `Server` with the Pallas backend, warms up the
shapes the cell's traffic uses, then serves the traffic from the seed for
`--seconds` of measured window and prints, as the last line of standard
output, one JSON object:

  {"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
   "check"}

With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 the window runs under the JAX profiler and the metrics are the
cell's per-layer ones (each read by bench/metrics/<name>.py), with the
device's busy and traced seconds and a breakdown of where the time went.
`check` holds each number that decided `correct` beside its limit
(bench/check.py); the same lines end standard error.

Fails, printing no result, without a TPU, with fewer chips than the cell
asks for, or when anything compiles inside the window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout, handed to the program through the variable it reads
#: (launch/serve.configure_compile_cache), so only a cell's first run in a
#: checkout compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

#: where a traced run writes its profile (inside the checkout, listed in
#: .gitignore; removed again once read)
TRACE_DIR = os.path.join(ROOT, "bench", "out", "trace")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def percentile(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, np.float64), q))


@dataclasses.dataclass
class Run:
    """What one run observed; the per-layer readers read it."""
    conf: dict
    mix: dict
    slots: int
    obs: dict
    ticks: list
    t_open: float
    t_close: float
    trace_end: float
    trace: object        # trace.Reduction, or None
    cost: object         # cost.ModelCost
    peaks: dict
    stats: tuple         # Server.stats when the window opened and closed

    def window_ticks(self):
        return [t for t in self.ticks
                if t.t0 >= self.t_open and t.t1 <= self.t_close]

    def traced_ticks(self):
        return [t for t in self.ticks
                if t.t0 >= self.t_open and t.t1 <= self.trace_end]


def end_to_end(run: Run, seconds: float) -> dict:
    """Every end-to-end metric this run can read, by name."""
    lo, hi = run.t_open, run.t_close
    out = {}
    ttft = [(ob.tok_t[0] - ob.due) * 1e3 for ob in run.obs.values()
            if ob.tok_t and lo <= ob.tok_t[0] < hi]
    if ttft:
        out["ttft_p90_ms"] = percentile(ttft, 90)
    itl = [(b - a) * 1e3 for ob in run.obs.values()
           for a, b in zip(ob.tok_t, ob.tok_t[1:]) if lo <= b < hi]
    if itl:
        out["itl_p95_ms"] = percentile(itl, 95)
    toks = sum(1 for ob in run.obs.values() for t in ob.tok_t if lo <= t < hi)
    out["tok_s"] = toks / seconds
    return out


def where_gaps(obs, ticks, lo, hi) -> str:
    """Where the gaps between tokens fall: how many end at a step that
    also ran a whole-prompt prefill, and the p95 of each kind."""
    pre = {t.t1 for t in ticks if t.admitted}
    both = [((b - a) * 1e3, b in pre) for ob in obs.values()
            for a, b in zip(ob.tok_t, ob.tok_t[1:]) if lo <= b < hi]
    if not both:
        return "no gaps in the window"
    with_p = [g for g, p in both if p]
    without = [g for g, p in both if not p]
    fmt = lambda xs: f"{percentile(xs, 95):.1f}" if xs else "-"
    return (f"{len(both)} gaps in the window, {len(with_p) / len(both):.1%} "
            f"end at a step that ran a prefill (p95 {fmt(with_p)} ms), the "
            f"rest p95 {fmt(without)} ms")


def device_line(devs, stats) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 3


def main(argv=None) -> int:
    args = parse(argv)
    from bench import spec
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, args.workload)
    conf = spec.config(bench, cell["config"], ROOT)
    mix = spec.traffic(cell["traffic"])

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < int(cell["chips"]):
        return fail(f"{args.workload} needs {cell['chips']} chips, "
                    f"found {len(devs)}")
    devs = devs[:int(cell["chips"])]
    from repro.kernels import dispatch
    if dispatch.pallas_interpret():
        return fail("Pallas would run interpreted")
    from repro.launch.serve import configure_compile_cache
    cache_dir = configure_compile_cache()
    cache_settings()
    from bench import cost
    pk = cost.peaks(devs[0].device_kind)
    held = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"bench: {args.workload} on {devs[0].device_kind} x{len(devs)}, "
          f"compile cache {cache_dir} ({held} files)", file=sys.stderr,
          flush=True)
    try:
        result = measure(args, bench, cell, conf, mix, devs, pk)
    except WindowCompiled as e:
        return fail(str(e))
    for name, r in result["check"].items():
        print(f"check {name}: {r['value']} limit {r['limit']}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


def cache_settings():
    """Keep every program in the persistent cache, however small or quick
    to compile, and never evict. A size bound (which the environment may
    set) switches JAX to a cache that keeps an access-time file beside each
    entry and fails every write once one entry lacks it, as entries that an
    unbounded cache wrote do: the checkout's cache would then never fill,
    and every run would compile."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


class WindowCompiled(RuntimeError):
    """Something traced or compiled inside the measured window."""


def measure(args, bench, cell, conf, mix, devs, pk) -> dict:
    """Set up, serve the window, check, and return the result object.
    Runs on whatever devices JAX has (the tests drive it on the CPU)."""
    import jax
    from bench import check, client, cost, reference, spec, trace
    wseed = args.seed % 2**32
    cfg, srv = client.build_server(conf, mix, wseed)
    client.warm_up(srv, mix, cfg.vocab)
    jax.block_until_ready(srv.cache)
    watch = client.CompileWatch()
    counts = dict(srv.compile_counts)
    gen = spec.generator(mix["generator"])(mix, args.seed, cfg.vocab)
    setup_s = time.perf_counter() - T_START
    print(f"bench: set-up {setup_s:.2f} s", file=sys.stderr, flush=True)

    cap = trace.Capture(TRACE_DIR) if args.trace else None

    stats = []

    def on_open(_t):
        watch.on = True
        stats.append(copy.deepcopy(srv.stats))
        if cap:
            cap.open()

    obs, ticks, t_open, t_close, _ = client.serve(
        srv, gen, seconds=args.seconds,
        open_after=int(mix["open_after_first_tokens"]), on_open=on_open,
        span=trace.span if cap else client.no_span)
    watch.on = False
    stats.append(copy.deepcopy(srv.stats))
    trace_end = time.perf_counter()
    red = cap.close() if cap else None
    if watch.events or srv.compile_counts != counts:
        raise WindowCompiled(
            f"compiled inside the window: {watch.events} trace or compile "
            f"events, signatures {counts} -> {srv.compile_counts}")
    mem = devs[0].memory_stats() or {}
    t_check = time.perf_counter()

    # -- correctness, once the window has closed -----------------------------
    chk = conf["check"]
    picks = check.gather(srv, args.seed, int(chk["requests"]),
                         int(chk["kv_layers"]))
    check.free(srv)
    del srv
    model = reference.Model.from_config(conf)
    read = check.readings(model, wseed, picks, int(mix["cache_len"]),
                          int(chk["kv_layers"]), int(conf["page_size"]))
    correct, numbers = check.verdict(read, chk["limits"])
    print("bench: " + where_gaps(obs, ticks, t_open, t_close), file=sys.stderr)
    print(f"bench: window {t_close - t_open:.2f} s from {t_open - T_START:.2f}"
          f" s, check {time.perf_counter() - t_check:.2f} s; readings "
          + json.dumps(read), file=sys.stderr, flush=True)

    run = Run(conf, mix, int(mix["slots"]), obs, ticks, t_open, t_close,
              trace_end if cap else t_close, red,
              cost.ModelCost.from_config(conf), pk, tuple(stats))
    late = gen.late_s
    # every request sent is attempted; the server refuses or fails none
    # (a refusal raises out of the run)
    result = {"correct": bool(correct), "attempted": len(obs), "failed": 0}
    metrics = {}
    if cap:
        for m in spec.metrics_for(bench, args.workload, "per_layer"):
            v = spec.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(run, args.seconds)
        e2e["setup_s"] = setup_s
        for m in spec.metrics_for(bench, args.workload, "end_to_end"):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device_line(devs, mem)
    if red is not None:
        result["device"]["busy_s"] = red.busy_s()
        result["device"]["window_s"] = red.window_s()
        result["breakdown"] = {"device_ops": red.top_ops(),
                               "idle_gaps": red.idle_gaps()}
    result["generator_late_ms_p95"] = (percentile(late, 95) * 1e3
                                       if late else None)
    result["check"] = numbers
    return result


if __name__ == "__main__":
    sys.exit(main())
