"""The MoE cell's per-layer readers (metrics/expert_gemm_roofline.py,
metrics/expert_row_live_share.py) on synthetic runs, against numbers
worked by hand."""
import pytest

from bench import cost, spec
from bench.client import Tick
from bench.run import Run

ROOFLINE = spec.metric_reader("expert_gemm_roofline")
LIVE = spec.metric_reader("expert_row_live_share")

#: 4 experts top-2 of width 4 on d 8, int8: one call on 2 tokens is 4
#: assignments, 2 x 4 x (8 x 8 + 4 x 8) = 768 operations; 4 x (1 - (1 -
#: 2/4)^2) = 3 experts touched, each 96 B of int8 weights and 4 x 16 B of
#: scales; rows 2 B x 4 x ((8 + 8) + (4 + 8)) = 224 B: 704 B in all
EXPERTS = cost.Experts(4, 2, 8, 4, "int8", "int8")
#: 768 ops take 1 s at the int8 peak, 704 B take 2 s: memory bounds a call
PEAKS = {"int8_ops": 768.0, "bf16_flops": 384.0, "hbm_bytes_per_s": 352.0}


class Trace:
    """Device seconds by kind, as trace.Reduction.kernel_s reads them."""

    def __init__(self, **secs):
        self.secs = secs

    def kernel_s(self, kind):
        return self.secs.get(kind, 0.0), int(kind in self.secs)


def _cost(experts=EXPERTS, layers=2):
    lm = cost.Linear("lm_head", 8, 16, "int8", "int8")
    return cost.ModelCost(tuple(((), experts) for _ in range(layers)), lm,
                          2, 2, 4)


def _run(trace=None, ticks=(), stats=({}, {}), model=None, slots=4):
    return Run(conf={}, mix={}, slots=slots, obs={}, ticks=list(ticks),
               t_open=0.0, t_close=10.0, trace_end=10.0, trace=trace,
               cost=model or _cost(), peaks=PEAKS, stats=tuple(stats))


def _decode(t0, positions):
    return Tick(t0, t0 + 0.1, 0, (), tuple(positions))


def test_expert_work_by_hand():
    assert EXPERTS.touched(2) == 3.0
    assert EXPERTS.work(2) == (768.0, 3 * (96 + 64) + 224)
    assert cost.least_time(*EXPERTS.work(2), EXPERTS.narrow(), PEAKS) == 2.0


def test_roofline_is_silent_without_a_trace_or_experts():
    ticks = [_decode(1.0, (5, 9))]
    assert ROOFLINE(_run(ticks=ticks)) is None
    assert ROOFLINE(_run(Trace(vmap_jit_gemm__=8.0), ticks,
                         model=_cost(layers=0))) is None
    # no expert GEMM in the trace (gemm alone is the dense cells')
    assert ROOFLINE(_run(Trace(gemm=8.0), ticks)) is None


def test_roofline_by_hand():
    """Two decode ticks of 2 tokens and a 2-token prompt, 2 expert layers:
    6 calls of 2 s least time over 48 s of vmap_jit_gemm__: 25%. The
    plain gemm kind is not the experts' and is not counted."""
    ticks = [_decode(1.0, (5, 9)),
             Tick(2.0, 2.1, 1, (2,), (6, 10)),
             _decode(20.0, (7, 11))]                  # after the trace
    trace = Trace(vmap_jit_gemm__=48.0, gemm=100.0)
    run = _run(trace, ticks)
    run.trace_end = 10.0
    assert ROOFLINE(run) == pytest.approx(100.0 * 6 * 2.0 / 48.0)


def test_roofline_counts_no_padded_row():
    """The work is the tokens' assignments: a server with more slots (more
    padded capacity rows a decode) reading the same device time for the
    same tokens reads the same share, and a dispatch that spends half the
    device time on them reads twice the share."""
    ticks = [_decode(1.0, (5, 9))]
    small = ROOFLINE(_run(Trace(vmap_jit_gemm__=16.0), ticks, slots=4))
    wide = ROOFLINE(_run(Trace(vmap_jit_gemm__=16.0), ticks, slots=64,
                         stats=({"moe_expert_rows": 0},
                                {"moe_expert_rows": 10**6})))
    assert small == wide == pytest.approx(100.0 * 2 * 2.0 / 16.0)
    assert ROOFLINE(_run(Trace(vmap_jit_gemm__=8.0), ticks)) == \
        pytest.approx(2 * small)


def _stats(routed, dropped, rows):
    return {"moe_routed": routed, "moe_dropped": dropped,
            "moe_expert_rows": rows}


def test_live_share_is_silent_without_the_counter():
    # the parent's server keeps routed and dropped but no row counter
    old = {"moe_routed": 10, "moe_dropped": 1}
    assert LIVE(_run(stats=(old, old))) is None
    assert LIVE(_run(stats=({}, {}))) is None        # a dense model
    assert LIVE(_run(stats=(_stats(5, 0, 100), _stats(5, 0, 100)))) is None


def test_live_share_by_hand():
    """16 slots x top-6 over 64 experts x 16 slots x capacity 4, 28 layers,
    10 decode ticks in the window: 96 / 4096 = 2.34375%. Dropped
    assignments are not live; the counts before the window are not the
    window's."""
    a = _stats(routed=5000, dropped=700, rows=90000)
    ticks, layers = 10, 28
    b = _stats(routed=5000 + ticks * layers * 16 * 6, dropped=700,
               rows=90000 + ticks * layers * 64 * 16 * 4)
    assert LIVE(_run(stats=(a, b))) == 100.0 * 96 / 4096 == 2.34375
    b["moe_dropped"] += ticks * layers * 48
    assert LIVE(_run(stats=(a, b))) == 100.0 * 48 / 4096
