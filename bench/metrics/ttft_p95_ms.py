"""95th percentile over every request due in the window of the time from
when it was due to the end of the pump
after which its first tokens were on the host. A request that failed
counts with the time until the drain ended."""
import numpy as np


def read(run):
    vals = [((run.drain_end if r.failed or r.first is None else r.first)
             - r.due) for r in run.records]
    return float(np.percentile(vals, 95) * 1e3) if vals else None
