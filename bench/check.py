"""Whether what the timed path produced is correct.

Once the window has closed, the server stops where it is. Its in-flight
requests hold, in their pages, the keys and values that the timed path
wrote at every position so far (whole-prompt prefill, the KV scatter, every
decode step through the paged cache), and the tokens it served. From those
the check draws a sample from the seed, the request with the longest
context always in it, copies their pages of the first `kv_layers` layers to
the host, and frees the program. The plain reference (bench/reference.py)
then runs each sample's prompt and served tokens teacher-forced, from its
own weights, and three numbers are compared with the limits the
configuration file states:

  kv_layer<l>   relative L2 gap between the program's cached keys and values
                of layer l and the reference's, over the sampled requests'
                positions, the worst request (layer 0 covers the embedding,
                the first block's int8 qkv projection, rotary phases and the
                KV writes; layer 1 adds layer 0's attention (flash or
                blockwise prefill, paged decode), its int8 out/up/down
                projections and layer 1's ternary qkv);
  token_rank_median
                the median, over every greedy served token of the sampled
                requests, of its rank among the reference's logits at its
                position (how many tokens the reference scores above it;
                0 = the reference's argmax).

Why keys and values of the first layers, and a median rank rather than the
widest logit gap: with random weights the model's ternary activation
quantizer turns a last-bit difference into an O(1) one within a few
layers, so by depth 32 a sound program's logits and the reference's part
about as far as a lower precision's do (PERF.md, Findings). The
early layers are compared before that happens. At the logits, the widest
gap of a sound run reads about half of what a wrong token reads and the
median gap about a third, while a sound run's served tokens still sit
among the reference's first few dozen of tens of thousands and a wrong
token's anywhere: the median rank catches a served token that is not the
model's. The gaps are printed beside the numbers, not compared.
"""
from __future__ import annotations

import gc

import jax
import numpy as np

from bench import reference


def sample(srv, run_seed: int, n: int, greedy: bool = False):
    """In-flight (slot, request) pairs, greedy ones only if `greedy`: the
    longest context and n-1 more drawn from the seed, in slot order."""
    from repro.launch.serve import RUNNING
    cand = [s for s, r in enumerate(srv.slot_req)
            if r is not None and r.state == RUNNING and srv.slot_pos[s] > 1
            and (r.temperature <= 0 or not greedy)]
    if not cand:
        return []
    longest = max(cand, key=lambda s: (int(srv.slot_pos[s]), -s))
    rest = [s for s in cand if s != longest]
    rng = np.random.default_rng((int(run_seed), 3, int(greedy)))
    pick = [int(s) for s in rng.permutation(rest)[:max(n - 1, 0)]]
    return [(s, srv.slot_req[s]) for s in sorted([longest] + pick)]


def _leaf(cache, layer: int, n_layers: int):
    """Layer `layer`'s K/V leaves in the server's cache: "first", then the
    scanned periods ("mid", b<i> of each period), any remainder layers
    ("rem<t>"), then "last"."""
    if layer == 0:
        return cache["first"]
    if layer == n_layers - 1:
        return cache["last"]
    mid = cache.get("mid", {})
    per = len(mid)
    periods = jax.tree.leaves(mid)[0].shape[0] if per else 0
    i = layer - 1
    if i < per * periods:
        return jax.tree.map(lambda a: a[i // per], mid[f"b{i % per}"])
    return cache[f"rem{i - per * periods}"]


def gather(srv, run_seed: int, n: int, kv_layers: int) -> list[dict]:
    """Host copies of the sampled requests' tokens and cached K/V of the
    first `kv_layers` layers at every position written so far: n requests
    for the K/V comparison and n greedy ones for the logit gap."""
    cfg = srv.cfg
    kv_picks = dict(sample(srv, run_seed, n))
    gap_picks = dict(sample(srv, run_seed, n, greedy=True))
    out = []
    for s in sorted(set(kv_picks) | set(gap_picks)):
        req = srv.slot_req[s]
        npos = int(srv.slot_pos[s])
        ids = srv.pt.slot_pages(s)
        seq = np.concatenate([np.asarray(req.prompt, np.int32),
                              np.asarray(req.out[:-1], np.int32)])
        if len(seq) != npos:
            raise RuntimeError(f"slot {s}: {len(seq)} tokens but position "
                               f"{npos}")
        kv = []
        for layer in range(kv_layers):
            leaf = _leaf(srv.cache, layer, cfg.n_layers)
            k, v = (np.asarray(leaf[name][ids]).reshape(
                -1, cfg.n_kv_heads, cfg.head_dim)[:npos]
                for name in ("k", "v"))
            kv.append((k, v))
        out.append({"seq": seq, "served": np.asarray(req.out, np.int32),
                    "prompt_len": len(req.prompt),
                    "kv_pick": s in kv_picks, "gap_pick": s in gap_picks,
                    "kv": kv})
    return out


def free(srv):
    """Drop the program's device state before the reference runs."""
    srv.cache = srv.params = None
    srv.slot_req = [None] * len(srv.slot_req)
    gc.collect()


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def ref_run(model, seed, picks, length, page_size, **kw):
    """reference.run over the picks' sequences."""
    return reference.run(model, seed, [p["seq"] for p in picks], length,
                         prompts=[p["prompt_len"] for p in picks],
                         page_size=page_size, **kw)


def readings(model: reference.Model, seed: int, picks: list[dict],
             length: int, kv_layers: int, page_size: int) -> dict:
    """The numbers compared: kv_layer<l> and token_rank_median (see module
    doc), and the logit gaps, printed only."""
    out: dict = {}
    kvp = [p for p in picks if p["kv_pick"]]
    kv, _ = ref_run(model, seed, kvp, length, page_size,
                    kv_layers=kv_layers, depth=kv_layers)
    for layer in range(kv_layers):
        out[f"kv_layer{layer}"] = max(
            max(rel_l2(p["kv"][layer][0], r[layer][0]),
                rel_l2(p["kv"][layer][1], r[layer][1]))
            for p, r in zip(kvp, kv))
    greedy = [p for p in picks if p["gap_pick"]]
    if greedy:
        g, r = (np.concatenate(x) for x in zip(*scores(
            model, seed, greedy, length, page_size)))
        out["token_rank_median"] = float(np.median(r))
        out["logit_gap_median"] = float(np.median(g))
        out["logit_gap_max"] = float(g.max())
    return out


def positions(p: dict) -> np.ndarray:
    """Positions whose logits chose the served tokens: the prompt's last
    position, then each served token's own."""
    return np.arange(p["prompt_len"] - 1, p["prompt_len"] - 1
                     + len(p["served"]))


def scores(model, seed, picks, length, page_size, tokens=None) -> list:
    """Per pick, at each position: (the reference's best logit minus its
    logit of the served token (or of `tokens[i]`), that token's rank)."""
    tokens = tokens or [p["served"] for p in picks]
    _, sc = ref_run(model, seed, picks, length, page_size,
                    positions=[positions(p) for p in picks],
                    cands=[t[:, None] for t in tokens])
    return [(best - got[:, 0], rank[:, 0]) for best, _, got, rank in sc]


def verdict(read: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the numbers that have a
    limit. A number the run could not read (no greedy request in flight)
    is missing, and fails."""
    out = {k: {"value": read.get(k), "limit": v} for k, v in limits.items()}
    ok = all(r["value"] is not None and r["value"] <= r["limit"]
             for r in out.values())
    return ok, out
