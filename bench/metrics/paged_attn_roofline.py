"""Kernels: the paged decode attention kernel's (kernels/paged_attn.py,
pallas_call "paged_flash_decode") least time over its device time. Each
decode tick must read, in every layer, the cached keys and values of every
active slot's positions so far; least time is those bytes at HBM
bandwidth."""

#: the kernel's kind in the device trace (its pallas_call name)
KERNEL = "paged_flash_decode"


def read(run):
    if run.trace is None:
        return None
    spent = run.trace.kernel_s(KERNEL)[0]
    layers = len(run.cost.layers)
    need = sum(layers * run.cost.decode_kv_bytes(t.decoded)
               for t in run.traced_ticks() if t.decoded)
    if not spent or not need:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / spent
