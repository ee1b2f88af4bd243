"""Frontend layer (``runtime/frontend.py``): mean over the window's
requests of the time from due to the end of the first pump after
which the ticket was admitted (RUNNING), on the harness's host clock."""
import numpy as np


def read(run):
    vals = [r.admitted - r.due for r in run.records if r.admitted is not None]
    return float(np.mean(vals) * 1e3) if vals else None
