"""The server's instrumentation in a trace (bench/programs.py): program runs
from the device's "XLA Modules" line, serve.* host spans with their stats,
and the four readings made from them, on synthetic planes worked out by
hand; the same answers as bench/trace.py's Reduction on traces without
serve.* spans; and the spans a tiny Server opens under the JAX profiler on
the CPU."""
import glob
import json
import os
import types

import jax
import numpy as np
import pytest

from bench import programs, trace
from bench.tests.test_bench_trace import DATA, ev, planes, synthetic


def span(name, start, end, **stats):
    return ev(name, start, end - start, **stats)


def server_planes():
    """Four ticks start in a [0, 1000) window: A decodes (host 200 - 120
    in sync = 80 ns), B admits rid 7 (300 prompt tokens, a 130 ns
    prefill), C decodes after a fenced plan (host 142 - 50 = 92 ns), D
    decodes and runs past the window (host 200 - 140 = 60 ns)."""
    host = [
        span("bench.window", 0, 1000),
        span("bench.step", 95, 305),
        span("serve.step", 100, 300, plan_hit=1),
        span("serve.dispatch", 110, 130, slots=2, chunk=0),
        span("serve.plan", 130, 160, ahead=1),
        span("serve.sync", 160, 280, what="decode"),
        span("serve.retire", 280, 290),
        span("bench.step", 305, 705),
        span("serve.step", 310, 700, plan_hit=1),
        span("serve.dispatch", 312, 330, slots=2, chunk=0),
        span("serve.plan", 330, 600, ahead=1),
        span("serve.admit", 340, 590, rid=7, tokens=300, bucket=512),
        span("serve.sync", 360, 540, what="prefill"),
        span("serve.scatter", 540, 580, rid=7),
        span("serve.sync", 600, 660, what="decode"),
        span("serve.retire", 660, 680),
        span("bench.step", 705, 855),
        span("serve.step", 708, 850, plan_hit=0),
        span("serve.plan", 710, 720, ahead=0),
        span("serve.dispatch", 720, 730, slots=1, chunk=0),
        span("serve.sync", 730, 780, what="decode"),
        span("serve.retire", 800, 840),
        span("bench.step", 890, 1100),
        span("serve.step", 895, 1095, plan_hit=1),
        span("serve.dispatch", 896, 950, slots=2, chunk=0),
        span("serve.sync", 950, 1090, what="decode"),
    ]
    return planes({
        "/device:TPU:0": {
            "XLA Modules": [span("jit_serve_decode(5)", 120, 250),
                            span("jit_argmax(9)", 250, 260),
                            span("jit_serve_decode(5)", 320, 400),
                            span("jit_serve_prefill(7)", 400, 530),
                            span("jit_dynamic_slice(3)", 530, 535),
                            span("jit_scatter(11)", 545, 575),
                            span("jit_serve_decode(5)", 725, 775),
                            span("jit_serve_decode(5)", 950, 1100)],
            "XLA Ops": [span("%fusion.1 = f32[2] fusion()", 120, 170),
                        span("%paged_flash_decode.2 = bf16[2]", 170, 240),
                        span("%reduce.3 = s32[2] reduce()", 250, 258),
                        span("%gemm.4 = bf16[2]", 320, 390),
                        span("%while.5 = (...) while()", 400, 530),
                        span("%gemm.6 = bf16[9]", 410, 500),
                        span("%flash_attention.7 = bf16[9]", 500, 525),
                        span("%dynamic-slice.8 = f32[8]", 530, 534),
                        span("%scatter.9 = bf16[4]", 545, 575),
                        span("%gemm.10 = bf16[1]", 725, 770),
                        span("%copy.12 = bf16[1] copy()", 900, 910),
                        span("%gemm.11 = bf16[2]", 950, 1100)]},
        "/host:CPU": {"python3": host},
    })


@pytest.fixture
def red():
    return programs.ProgramReduction.from_planes(server_planes())


def test_programs_by_device_self_time(red):
    # decode: 50 + 70 (A) + 70 (B) + 45 (C) + 50 (D, clipped at the
    # window's end); prefill: the gemm and flash inside its while; eager:
    # argmax, the logits slice and the scatter; the copy is in no module
    got = dict(red.programs())
    assert got == {"jit_serve_decode": pytest.approx(285e-9),
                   "jit_serve_prefill": pytest.approx(115e-9),
                   "eager": pytest.approx(42e-9),
                   "outside": pytest.approx(10e-9)}
    assert [n for n, _ in red.programs()][0] == "jit_serve_decode"
    assert [o.kind for o in red.ops_in("jit_serve_prefill")] == [
        "while", "gemm", "flash_attention"]
    # runs are kept whole: D's 150 ns count though the window cuts it
    assert red.program_s("jit_serve_decode") == (pytest.approx(410e-9), 4)
    assert red.program_s("jit_traced") == (0.0, 0)


def test_the_four_readings(red):
    assert programs.decode_device_ms(red) == pytest.approx(410e-6 / 4)
    # 130 ns of prefill for 300 tokens
    assert programs.prefill_ms_per_ktok(red) == pytest.approx(
        130e-6 / 0.3)
    # ticks A, C and D; B admitted
    assert programs.tick_host_ms(red) == pytest.approx(
        (80 + 92 + 60) / 3 / 1e6)
    # idle under serve.* spans other than serve.sync, of 1000 ns:
    # serve.step 75, dispatch 67, retire 70, scatter 10, admit 10, plan 20
    assert programs.server_idle_share(red) == pytest.approx(25.2)
    assert set(programs.READINGS) == {"decode_device_ms",
                                      "prefill_ms_per_ktok", "tick_host_ms",
                                      "server_idle_share"}


def test_idle_time_by_innermost_span(red):
    idle = red.idle_under()
    want = {"outside": 130, "step": 33, "serve.step": 75,
            "serve.dispatch": 67, "serve.sync": 118, "serve.retire": 70,
            "serve.scatter": 10, "serve.admit": 10, "serve.plan": 20}
    assert idle == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    assert sum(idle.values()) == pytest.approx(red.window_s()
                                               - red.busy_s())
    # the longest gaps inside a tick are named by the server's phase
    gaps = red.idle_gaps(3)
    assert gaps == [["serve.sync", pytest.approx(150e-9)],
                    ["serve.retire", pytest.approx(130e-9)],
                    ["outside", pytest.approx(120e-9)]]
    assert red.host_at(365) == "serve.sync"
    assert red.host_at(345) == "serve.admit"
    assert red.host_at(302) == "step"


def test_spans_keep_their_stats_and_nest(red):
    admits = red.program_spans_in(*red.window(), "serve.admit")
    assert [a for *_, a in admits] == [{"rid": 7, "tokens": 300,
                                        "bucket": 512}]
    _, s, e, _ = admits[0]
    assert [n for n, *_ in red.program_spans_in(s, e)] == [
        "serve.admit", "serve.sync", "serve.scatter"]
    # at one start the enclosing span sorts first
    assert programs.ProgramReduction.from_planes(planes({"/host:CPU": {
        "t": [span("serve.sync", 5, 8), span("serve.step", 5, 9)]}})
    ).program_spans[0][0] == "serve.step"


def test_no_server_spans_reads_nothing():
    red = programs.ProgramReduction.from_planes(planes({
        "/device:TPU:0": {"XLA Ops": [span("%gemm.1 = bf16[2]", 10, 20)],
                          "XLA Modules": [span("jit_traced(3)", 5, 30)]},
        "/host:CPU": {"python3": [span("bench.window", 0, 100)]}}))
    assert all(f(red) is None for f in programs.READINGS.values())
    assert red.programs() == [["eager", pytest.approx(10e-9)]]


def _same_as_reduction(old, new):
    assert new.window() == old.window()
    assert new.busy_s() == old.busy_s()
    assert new.top_ops() == old.top_ops()
    assert new.idle_gaps() == old.idle_gaps()
    for k in {o.kind for ops in old.ops.values() for o in ops}:
        assert new.kernel_s(k) == old.kernel_s(k)


def test_without_server_spans_readings_match_reduction(monkeypatch):
    old = synthetic()
    monkeypatch.setattr(trace, "Reduction", programs.ProgramReduction)
    new = synthetic()
    assert isinstance(new, programs.ProgramReduction)
    _same_as_reduction(old, new)


def _recorded(path):
    with open(path) as f:
        rec = json.load(f)
    return rec, [types.SimpleNamespace(
        name=p["name"], lines=[types.SimpleNamespace(
            name=ln["name"], events=[types.SimpleNamespace(
                name=e["name"], start_ns=e["start_ns"],
                duration_ns=e["duration_ns"],
                stats=[tuple(s) for s in e["stats"]])
                for e in ln["events"]]) for ln in p["lines"]])
        for p in rec["planes"]]


def test_recorded_trace_without_server_spans_matches_reduction():
    _, pls = _recorded(DATA)
    _same_as_reduction(trace.Reduction.from_planes(pls),
                       programs.ProgramReduction.from_planes(pls))


# -- a tiny Server under the JAX profiler on the CPU ---------------------------

def _tiny_server():
    import dataclasses
    from repro.configs import get_config
    from repro.launch.serve import Server, serve_params
    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(),
                              policy="int8")
    params, _ = serve_params(cfg, seed=0)
    return cfg, Server(cfg, params, slots=2, cache_len=32, page_size=4)


def test_tiny_server_spans_under_the_profiler(tmp_path):
    from repro.launch.serve import Request
    cfg, srv = _tiny_server()
    rng = np.random.default_rng(3)
    lens = (5, 7, 6)

    def submit(base):
        for i, n in enumerate(lens):
            srv.submit(Request(base + i, rng.integers(0, cfg.vocab, n)
                               .astype(np.int32), 3))

    submit(100)                 # compile every program outside the trace
    srv.run()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.span("window"):
            submit(0)
            srv.run()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    red = programs.ProgramReduction.from_dir(os.path.dirname(found[0]))
    lo, hi = red.window()
    steps = red.program_spans_in(lo, hi, "serve.step")
    assert steps
    seen = set()
    for _, s, e, args in steps:
        assert args["plan_hit"] in (0, 1)
        seen |= {n for n, *_ in red.program_spans_in(s, e)}
    assert {"serve.plan", "serve.dispatch", "serve.sync",
            "serve.retire", "serve.admit", "serve.scatter"} <= seen
    # every span of the run sits inside a serve.step
    inside = sum(len(red.program_spans_in(s, e)) for _, s, e, _ in steps)
    assert inside == len(red.program_spans_in(lo, hi))
    admits = red.program_spans_in(lo, hi, "serve.admit")
    assert sorted((a["rid"], a["tokens"]) for *_, a in admits) == [
        (0, 5), (1, 7), (2, 6)]
    assert all(a["bucket"] == 8 for *_, a in admits)
    whats = {a["what"] for *_, a in red.program_spans_in(lo, hi,
                                                         "serve.sync")}
    assert whats == {"decode", "prefill"}
    _, s, e, _ = red.program_spans_in(lo, hi, "serve.sync")[0]
    assert red.host_at((s + e) // 2) == "serve.sync"


# -- a recorded cut of a chip trace (tests/data/trace_fixture_programs.json) ---

FIXTURE = os.path.join(os.path.dirname(DATA), "trace_fixture_programs.json")


def _fixture():
    with open(FIXTURE) as f:
        rec = json.load(f)
    pls = [types.SimpleNamespace(name=p["name"], lines=[
        types.SimpleNamespace(name=ln["name"], events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d,
                                  stats=[tuple(x) for x in st])
            for n, s, d, st in ln["events"]]) for ln in p["lines"]])
        for p in rec["planes"]]
    return rec["expect"], programs.ProgramReduction.from_planes(pls)


def test_recorded_chip_trace_readings():
    want, red = _fixture()
    for name, read in programs.READINGS.items():
        assert read(red) == pytest.approx(want[name], rel=1e-9), name
    assert dict(red.programs()) == pytest.approx(want["programs"],
                                                 rel=1e-9)
    assert red.idle_under() == pytest.approx(
        {k: v / 1e9 for k, v in want["idle_ns_by_span"].items()}, rel=1e-9)


def test_recorded_chip_trace_shares_one_clock():
    """Host spans and device runs are on one clock: a decode run starts
    after the serve.dispatch that launched it and before its tick ends; the
    prefill run lies inside its admission and ends before the host's wait
    on it does."""
    _, red = _fixture()
    lo, hi = red.window()
    dispatches = [d for d in red.program_spans_in(lo, hi, "serve.dispatch")
                  if d[3]["slots"] > 0]
    assert dispatches
    for _, s, _, _ in dispatches:
        step = [t for t in red.program_spans
                if t[0] == "serve.step" and t[1] <= s < t[2]][0]
        assert len(red.program_runs("jit_serve_decode", s, step[2])) == 1
        assert not red.program_runs("jit_serve_decode", step[1], s)
    (_, a0, a1, _), = red.program_spans_in(lo, hi, "serve.admit")
    (r0, r1), = red.program_runs("jit_serve_prefill", lo, hi)
    (_, _, w1, _), = [w for w in red.program_spans_in(a0, a1, "serve.sync")
                      if w[3]["what"] == "prefill"]
    assert a0 <= r0 and r1 <= w1 <= a1
