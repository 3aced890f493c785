"""Readings that set the limits of `correct`, on the chip, at a cell's size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 15]
                             [--out bench/out/control.jsonl]

For each seed, in one process: build the cell's server from that seed,
serve the cell's own traffic for a short window, stop, and read, from the
same in-flight requests, three sets of the numbers bench/check.py compares:

  sound     the program against the reference (what every run reads);
  control   the reference computed in the nearest precision below the
            configuration's (int8 layers as int4), put in the program's
            place: its keys and values against the reference's, and the
            reference rank and gap of the token it would put first;
  fault     the served tokens altered where they are produced (token + 1):
            their reference rank and gap.

One JSON line per seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def lower(model):
    """The control's precision: every int8 layer as int4 (weights), the
    step below the configuration's int8 that would tempt a later change."""
    down = lambda p: ("int4", p[1]) if p[0] == "int8" else p
    return dataclasses.replace(model, body=down(model.body),
                               first_last=down(model.first_last),
                               lm_head=down(model.lm_head))


def control_readings(model, seed, picks, length, kv_layers,
                     page_size) -> dict:
    """The control's numbers on the sound run's requests (see module doc)."""
    import numpy as np
    from bench import check
    ctrl = lower(model)
    kvp = [p for p in picks if p["kv_pick"]]
    ref_kv, _ = check.ref_run(model, seed, kvp, length, page_size,
                              kv_layers=kv_layers, depth=kv_layers)
    low_kv, _ = check.ref_run(ctrl, seed, kvp, length, page_size,
                              kv_layers=kv_layers, depth=kv_layers)
    out = {}
    for layer in range(kv_layers):
        out[f"kv_layer{layer}"] = max(
            max(check.rel_l2(lo[layer][0], r[layer][0]),
                check.rel_l2(lo[layer][1], r[layer][1]))
            for lo, r in zip(low_kv, ref_kv))
    greedy = [p for p in picks if p["gap_pick"]]
    if greedy:
        _, sc = check.ref_run(ctrl, seed, greedy, length, page_size,
                              positions=[check.positions(p) for p in greedy],
                              cands=[p["served"][:, None] for p in greedy])
        firsts = [am for _, am, _, _ in sc]
        for name, toks in (("", firsts), ("fault_", [
                (p["served"] + 1) % model.vocab for p in greedy])):
            g, r = (np.concatenate(x) for x in zip(*check.scores(
                model, seed, greedy, length, page_size, tokens=toks)))
            out[name + "token_rank_median"] = float(np.median(r))
            out[name + "logit_gap_median"] = float(np.median(g))
            out[name + "logit_gap_max"] = float(g.max())
    out["greedy_tokens"] = int(sum(len(p["served"]) for p in greedy))
    out["kv_positions"] = [int(len(p["seq"])) for p in kvp]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--kv-layers", type=int, default=None,
                    help="compare this many layers (default: the config's)")
    ap.add_argument("--out", default=os.path.join(ROOT, "bench", "out",
                                                  "control.jsonl"))
    args = ap.parse_args(argv)
    import jax
    from bench import check, client, reference, spec
    from repro.launch.serve import configure_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, args.workload)
    conf = spec.config(bench, cell["config"], ROOT)
    mix = spec.traffic(cell["traffic"])
    chk = conf["check"]
    n, length = int(chk["requests"]), int(mix["cache_len"])
    kvl = args.kv_layers or int(chk["kv_layers"])
    model = reference.Model.from_config(conf)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        wseed = seed % 2**32
        cfg, srv = client.build_server(conf, mix, wseed)
        client.warm_up(srv, mix, cfg.vocab)
        gen = spec.generator(mix["generator"])(mix, seed, cfg.vocab)
        client.serve(srv, gen, seconds=args.seconds,
                     open_after=int(mix["open_after_first_tokens"]))
        picks = check.gather(srv, seed, n, kvl)
        check.free(srv)
        del srv
        ps = int(conf["page_size"])
        row = {"workload": args.workload, "seed": seed,
               "sound": check.readings(model, wseed, picks, length, kvl, ps),
               "control": control_readings(model, wseed, picks, length, kvl,
                                           ps)}
        line = json.dumps(row)
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
