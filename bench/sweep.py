"""Find an open-loop cell's knee once, on the chip: serve its traffic at a
ladder of absolute rates and report, per rate, the waiting queue at the
window's start and end, TTFT and ITL tails, and the tokens per second
completed beside those offered (rate x the mix's mean output).

    python3 bench/sweep.py --workload <cell> --rates 1,1.5,2,3 [--seconds 30]
                           [--seed 5]

The knee is the highest rate at which the queue does not grow and the
tokens completed keep up with those offered. The window has to reach
steady state: with 25 s windows the queue read empty up to 1.75 req/s on
Phi-3 chat while 51 s runs at 1.4 req/s saturated (PERF.md). One
server serves every rate, drained empty between them; one JSON line per
rate. The benchmark's own runs never run this: the cell's traffic file
states the rate it serves, a number in req/s.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    from bench import client, spec
    from bench.run import end_to_end, Run
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, args.workload)
    conf = spec.config(bench, cell["config"], ROOT)
    mix = spec.traffic(cell["traffic"])
    cfg, srv = client.build_server(conf, mix, args.seed)
    client.warm_up(srv, mix, cfg.vocab)
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_rps=rate)
        gen = spec.generator(m["generator"])(m, args.seed, cfg.vocab)
        q = {}
        obs, ticks, t_open, t_close, _ = client.serve(
            srv, gen, seconds=args.seconds,
            open_after=int(m["open_after_first_tokens"]),
            on_open=lambda t: q.setdefault("start", len(srv.queue)))
        q["end"] = len(srv.queue)
        run = Run(conf, m, int(m["slots"]), obs, ticks, t_open, t_close,
                  t_close, None, None, None, ())
        mean_out = sum(sh.max_new for sh in gen.shapes) / len(gen.shapes)
        row = {"rate_rps": rate, "offered_tok_s": rate * mean_out,
               "queue_start": q["start"],
               "queue_end": q["end"], **end_to_end(run, args.seconds),
               "requests_in_window": sum(
                   1 for o in obs.values()
                   if o.tok_t and t_open <= o.tok_t[0] < t_close),
               "occupancy": (sum(len(t.decoded) for t in ticks
                                 if t.t0 >= t_open)
                             / max(1, sum(1 for t in ticks
                                          if t.t0 >= t_open and t.decoded))
                             / int(m["slots"]))}
        print(json.dumps(row), flush=True)
        srv.queue.clear()
        srv.run()
        srv.completed.clear()
        srv.pos_trace.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
