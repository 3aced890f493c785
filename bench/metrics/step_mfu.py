"""Device: model operations of every prompt and output token processed in
the traced window (2 x weights touched per token, logits where they are
taken, attention QK and PV at each position), each at the peak of its
operand width (<= 8 bits: int8 peak; else bf16), over the window."""


def read(run):
    if run.trace is None or not run.trace.on_device():
        return None
    cost, pk = run.cost, run.peaks
    need = 0.0
    for t in run.traced_ticks():
        if t.decoded:
            m = len(t.decoded)
            need += cost.model_least_s(m, m, sum(p + 1 for p in t.decoded),
                                       pk)
        for n in t.prefill_lens:
            need += cost.model_least_s(n, 1, n * (n + 1) / 2, pk)
    return 100.0 * need / run.trace.window_s() if need else None
