"""Kernels: the quantized GEMM cells' (kernels/harness.py, pallas_call
"gemm"; vmapped over an expert stack it is "vmap_jit_gemm__") least time
for the work the traced window needed, over their device time. The least time of each call is the larger of its operations
at the peak for its operand width and its packed weights plus bf16 rows at
HBM bandwidth (bench/cost.py); rows are real tokens, never padding."""

#: the kernel's kinds in the device trace (its pallas_call name, alone
#: and under the expert vmap)
KERNELS = ("gemm", "vmap_jit_gemm__")


def read(run):
    if run.trace is None:
        return None
    spent = sum(run.trace.kernel_s(k)[0] for k in KERNELS)
    need = 0.0
    for t in run.traced_ticks():
        if t.decoded:
            need += run.cost.qgemm_least_s(len(t.decoded), len(t.decoded),
                                           run.peaks)
        for n in t.prefill_lens:
            need += run.cost.qgemm_least_s(n, 1, run.peaks)
    if not spent or not need:
        return None
    return 100.0 * need / spent
