"""MoE dispatch: the share of the rows the expert GEMMs computed in the
window that held a routed assignment, from the server's counters:
100 x (routed - dropped) / expert rows, each the change of its
Server.stats counter (moe_routed, moe_dropped, moe_expert_rows) between
the window's open and close. The rows are E x B·C per layer and call, so
the dense capacity dispatch's padding is what keeps this low. None where
the server keeps no row counter."""

KEYS = ("moe_routed", "moe_dropped", "moe_expert_rows")


def read(run):
    if not all(k in s for s in run.stats for k in KEYS):
        return None
    a, b = run.stats
    d = {k: b[k] - a[k] for k in KEYS}
    if d["moe_expert_rows"] <= 0:
        return None
    return 100.0 * (d["moe_routed"] - d["moe_dropped"]) / d[
        "moe_expert_rows"]
