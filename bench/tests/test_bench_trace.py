"""The trace reduction: busy time as a union of device intervals, kernels
by name, idle gaps by the host span open across them, and the same on a
cut of a recorded chip trace (tests/data/trace_fixture.json)."""
import json
import os
import types

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_fixture.json")


def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def planes(spec):
    """spec: {plane: {line: [events]}} -> ProfileData-like planes."""
    return [types.SimpleNamespace(
        name=pn, lines=[types.SimpleNamespace(name=ln, events=evs)
                        for ln, evs in lines.items()])
        for pn, lines in spec.items()]


def synthetic():
    return trace.Reduction.from_planes(planes({
        "/device:TPU:0": {
            "XLA Ops": [ev("%fusion.1 = bf16[8] fusion(...)", 100, 50),
                        ev("%paged_flash_decode.2 = bf16[8,32,1,96] "
                           "custom-call(...)", 120, 60),         # overlaps
                        ev("%while.3 = (...) while(...)", 300, 100),
                        ev("%gemm.48 = bf16[8,16384] custom-call(...)",
                           310, 50),                             # in while
                        ev("%gemm.50 = bf16[8,3072] custom-call(...)",
                           370, 20),
                        ev("%fusion.7 = f32[2] fusion(...)", 700, 400)],
            "XLA Modules": [ev("jit_traced", 90, 400)]},
        "/host:CPU": {"python3": [ev("bench.window", 0, 1000),
                                  ev("bench.wait", 180, 120),
                                  ev("other", 0, 5)]},
    }))


def test_union_busy_window_and_kernels():
    r = synthetic()
    assert r.window() == (0, 1000)
    # [100, 180) + [300, 400) + [700, 1000) clipped to the window
    assert r.busy_s() == pytest.approx(480e-9)
    assert r.window_s() == pytest.approx(1e-6)
    assert r.kernel_s("paged_flash_decode") == (pytest.approx(60e-9), 1)
    assert r.kernel_s("gemm") == (pytest.approx(70e-9), 2)
    assert r.on_device()
    # the while holds the gemms: it is not a leaf, so it is not counted
    top = dict(r.top_ops())
    assert top == {"fusion": pytest.approx(350e-9),   # 50 + 300 clipped
                   "gemm": pytest.approx(70e-9),
                   "paged_flash_decode": pytest.approx(60e-9)}


def test_kind_strips_the_instruction_number():
    assert trace.kind("%gemm.48 = bf16[2,3] custom-call(x)") == "gemm"
    assert trace.kind("%copy-done = bf16[4]") == "copy-done"
    assert trace.kind("%dynamic-slice_bitcast_fusion.9 = u32") == \
        "dynamic-slice_bitcast_fusion"


def test_idle_gaps_name_the_host_span():
    gaps = synthetic().idle_gaps()
    # gaps [0,100) outside any inner span, [180,300) under bench.wait,
    # [400,700) outside
    assert gaps[0] == ["outside", pytest.approx(300e-9)]
    assert ["wait", pytest.approx(120e-9)] in gaps
    assert len(gaps) == 3


def test_union_of_nested_and_disjoint_intervals():
    assert trace.union_ns([(0, 10), (2, 5), (20, 30), (25, 40)]) == 30
    assert trace.union_ns([]) == 0


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_chip_trace():
    with open(DATA) as f:
        rec = json.load(f)
    r = trace.Reduction.from_planes([types.SimpleNamespace(
        name=p["name"], lines=[types.SimpleNamespace(
            name=ln["name"], events=[types.SimpleNamespace(
                name=e["name"], start_ns=e["start_ns"],
                duration_ns=e["duration_ns"], stats=e["stats"])
                for e in ln["events"]]) for ln in p["lines"]])
        for p in rec["planes"]])
    want = rec["expect"]
    assert r.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    assert r.window_s() == pytest.approx(want["window_s"], rel=1e-9)
    for name, (secs, n) in want["kernels"].items():
        got = r.kernel_s(name)
        assert got[1] == n and got[0] == pytest.approx(secs, rel=1e-9)
    assert 0 < r.busy_s() <= r.window_s()
