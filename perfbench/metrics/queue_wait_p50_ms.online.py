"""Median wait of the requests due in the window from when each was due to
when the scheduler gave it a lane (the engine's ``admit_t`` stamp)."""
import numpy as np


def read(run):
    v = [(s.req.admit_t - s.due) * 1e3 for s in run.due_in_window()
         if s.req.admit_t is not None]
    return float(np.percentile(v, 50)) if v else None
