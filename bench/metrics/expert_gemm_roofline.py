"""MoE experts: the routed-expert GEMMs' (the qgemm cells vmapped over an
expert stack, pallas_call "gemm" under the vmap: kind "vmap_jit_gemm__")
least time for the routed-expert work the traced window needed, over their
device time. The work is that of the real assignments only (tokens x
top_k of each decode tick and each admitted prompt, bench/cost.Experts):
the padded capacity rows the dense dispatch also computes count as none,
so a dispatch that computes fewer of them reads higher for the same
tokens. Least time per layer and call is the larger of its operations at
the peak for its operand width and its bytes at HBM bandwidth."""
from bench.cost import least_time

#: the expert GEMMs' kind in the device trace
KERNEL = "vmap_jit_gemm__"


def read(run):
    experts = run.cost.experts()
    if run.trace is None or not experts:
        return None
    spent = run.trace.kernel_s(KERNEL)[0]
    calls = [len(t.decoded) for t in run.traced_ticks() if t.decoded]
    calls += [n for t in run.traced_ticks() for n in t.prefill_lens]
    need = sum(least_time(*ex.work(m), ex.narrow(), run.peaks)
               for m in calls for ex in experts)
    if not spent or not need:
        return None
    return 100.0 * need / spent
