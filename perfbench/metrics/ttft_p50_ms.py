"""Median time to first token of the requests due in the window, each
timed from when it was due (open loop), on the host clock."""
import numpy as np


def read(run):
    v = [(s.req.first_token_t - s.due) * 1e3 for s in run.due_in_window()
         if s.req.first_token_t is not None]
    return float(np.percentile(v, 50)) if v else None
