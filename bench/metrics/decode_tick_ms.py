"""Model step: mean host-clock duration of the Server.step() calls inside
the window that decoded and admitted nothing (each call ends in the
fix-up's wait for the device): the window's total over their count."""


def read(run):
    ticks = [t for t in run.window_ticks() if t.decoded and not t.admitted]
    if not ticks:
        return None
    return 1e3 * sum(t.t1 - t.t0 for t in ticks) / len(ticks)
