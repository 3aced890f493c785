"""Scheduler: 90th percentile of the wait from a request's scheduled send to
its admission into a slot (the start of the Server.step() call that admitted
it), over requests admitted inside the window. Host clock."""
import numpy as np


def read(run):
    waits = [(ob.admit_t - ob.due) * 1e3 for ob in run.obs.values()
             if ob.admit_t is not None and run.t_open <= ob.admit_t
             < run.t_close]
    return float(np.percentile(waits, 90)) if waits else None
