"""95th percentile over the requests that completed of (last token seen -
first token seen) / (max_new_tokens - 1): the gap between output tokens."""
import numpy as np


def read(run):
    vals = [(r.last - r.first) / (r.req.max_new_tokens - 1)
            for r in run.records
            if not r.failed and r.first is not None and r.last is not None
            and r.req.max_new_tokens > 1]
    return float(np.percentile(vals, 95) * 1e3) if vals else None
