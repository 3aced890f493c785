"""Scheduler: mean share of the slots that decoded, over the decode ticks
inside the window (active positions per tick from Server.pos_trace)."""


def read(run):
    ticks = [t for t in run.window_ticks() if t.decoded]
    if not ticks:
        return None
    return 100.0 * sum(len(t.decoded) for t in ticks) / (len(ticks)
                                                        * run.slots)
