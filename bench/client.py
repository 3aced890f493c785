"""The system under test, driven from the client side.

Builds `repro.launch.serve.Server` for a configuration and a mix, warms up
the shapes the mix uses, then serves the mix's traffic and records, from
the benchmark's own clocks, when each request was due, when it was
admitted and when each of its tokens landed, plus one record per
`Server.step()` call. The program supplies only the server, its counters
(`stats`, `pos_trace`, `compile_counts`) and its request objects.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import numpy as np

clock = time.perf_counter


def arch_config(conf: dict):
    """The program's ArchConfig for a configuration file, checked against
    the sizes the file states."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(conf["arch"]), policy=conf["policy"],
                              **conf.get("arch_overrides", {}))
    s = conf["served"]
    want = {"n_layers": s["num_hidden_layers"], "d_model": s["hidden_size"],
            "n_heads": s["num_attention_heads"],
            "n_kv_heads": s["num_key_value_heads"],
            "head_dim": s["head_dim"], "vocab": s["vocab_size"],
            "rope_theta": s["rope_theta"],
            "d_ff": s.get("moe_intermediate_size", s["intermediate_size"]),
            "n_experts": s.get("n_routed_experts", 0),
            "n_shared_experts": s.get("n_shared_experts", 0),
            "top_k": s.get("num_experts_per_tok", 0),
            "param_dtype": conf["quant"]["param_dtype"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"{conf['arch']}: program sizes differ from the "
                         f"configuration file (program, file): {bad}")
    return cfg


@dataclasses.dataclass
class Obs:
    """One request as the client saw it."""
    rid: int
    due: float
    prompt_len: int
    admit_t: float | None = None
    tok_t: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Tick:
    """One Server.step() call."""
    t0: float
    t1: float
    admitted: int          # requests admitted (each with a whole prefill)
    prefill_lens: tuple    # their prompt lengths
    decoded: tuple         # positions of the slots that decoded, or ()


def build_server(conf: dict, mix: dict, seed: int):
    from repro.launch.serve import Server, serve_params
    from repro.models.common import ModelCtx
    cfg = arch_config(conf)
    params, _ = serve_params(cfg, seed=seed)
    jax.block_until_ready(params)
    srv = Server(cfg, params, slots=int(mix["slots"]),
                 cache_len=int(mix["cache_len"]),
                 page_size=int(conf["page_size"]),
                 num_pages=mix.get("num_pages"),
                 ctx=ModelCtx(mode="serve",
                              backend=conf.get("backend", "pallas")))
    return cfg, srv


def warm_buckets(srv, lo: int, hi: int) -> list[int]:
    """Prompt lengths that hit every prefill bucket of [lo, hi], once."""
    out, prev = [], 0
    for b in srv.buckets:
        if b >= lo and prev < hi:
            out.append(max(lo, min(b, hi)))
        prev = b
    return out


def warm_up(srv, mix: dict, vocab: int):
    """Serve one short greedy request per prefill bucket the mix can reach
    and, where the mix samples, one sampled request beside them: every
    program and every host-side conversion the window will run is then
    compiled (or loaded from the persistent cache)."""
    from repro.launch.serve import Request
    rng = np.random.default_rng(0)
    lens = warm_buckets(srv, int(mix["prompt_len"]["min"]),
                        int(mix["prompt_len"]["max"]))
    rounds = [[(n, 0.0) for n in lens]]
    if float(mix.get("greedy_share", 1.0)) < 1.0:
        rounds.append([(lens[0], float(mix["temperature"]))])
    rid = -1
    for reqs in rounds:       # a greedy batch, then a sampled one alone
        for n, temp in reqs:
            srv.submit(Request(rid, rng.integers(0, vocab, n).astype(
                np.int32), 3, temperature=temp, seed=7))
            rid -= 1
        srv.run()
    srv.completed.clear()
    srv.pos_trace.clear()
    return lens


class CompileWatch:
    """Counts JAX traces and compiles (any program, eager ops included)
    through jax.monitoring, so a run can prove its window compiled nothing."""

    def __init__(self):
        self.events = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, name, *_args, **_kw):
        if self.on and ("compile" in name or "trace" in name):
            self.events += 1


def no_span(_name):
    return contextlib.nullcontext()


def serve(srv, gen, *, seconds: float, open_after: int, on_open=None,
          span=no_span, max_s: float = 300.0):
    """Serve `gen`'s traffic; the window opens once `open_after` requests
    have their first token and lasts `seconds`. `span(name)` wraps the
    loop's phases ("submit", "step", "observe", "wait") for a trace.
    Returns (obs {rid: Obs}, ticks [Tick], t_open, t_close, live)."""
    from repro.launch.serve import Request
    obs: dict[int, Obs] = {}
    live: dict[int, Request] = {}
    ticks: list[Tick] = []
    first_tokens = 0
    t_open = t_close = None
    start = clock()
    gen.start(start)
    while True:
        now = clock()
        if t_close is not None and now >= t_close:
            break
        if now - start > max_s:
            raise RuntimeError(f"window did not close within {max_s} s "
                               f"({first_tokens} first tokens)")
        with span("submit"):
            for s in gen.due(now):
                req = Request(s.rid, s.prompt, s.max_new,
                              temperature=s.temperature, seed=s.seed)
                srv.submit(req)
                live[s.rid] = req
                obs[s.rid] = Obs(s.rid, s.due, len(s.prompt))
        if not live:
            nxt = gen.next_due()
            wait = 0.001 if nxt is None else max(0.0, nxt - clock())
            if t_close is not None:
                wait = min(wait, max(0.0, t_close - clock()))
            with span("wait"):
                time.sleep(wait)
            continue
        adm0 = srv.stats["admitted"]
        npos = len(srv.pos_trace)
        with span("step"):
            t0 = clock()
            srv.step()
            t1 = clock()
        with span("observe"):
            plens, firsts = _observe(srv, live, obs, gen, t0, t1)
        first_tokens += firsts
        decoded = (tuple(int(p) for p in srv.pos_trace[-1])
                   if len(srv.pos_trace) > npos else ())
        ticks.append(Tick(t0, t1, srv.stats["admitted"] - adm0, tuple(plens),
                          decoded))
        if t_open is None and first_tokens >= open_after:
            t_open = t1
            t_close = t_open + seconds
            if on_open is not None:
                on_open(t_open)
    gen.stop()
    return obs, ticks, t_open, t_close, live


def _observe(srv, live, obs, gen, t0, t1):
    """Record what one step changed; returns (lengths of the prompts
    admitted, first tokens landed)."""
    from repro.launch.serve import WAITING
    plens, firsts = [], 0
    for rid in list(live):
        req, ob = live[rid], obs[rid]
        if ob.admit_t is None and req.state != WAITING:
            ob.admit_t = t0
            plens.append(ob.prompt_len)
        new = len(req.out) - len(ob.tok_t)
        if new > 0:
            firsts += not ob.tok_t
            ob.tok_t.extend([t1] * new)
        if req.done:
            del live[rid]
            gen.completed(rid, t1)
    return plens, firsts
