"""Engine layer (``runtime/serve.py``): mean over the tickets submitted
and admitted in the traced window, whose first tokens reached the host in
it, of the time from the start of their admitting ``frontend.admit`` span
to the engine's ``request.first_token`` event for the same request
(joined through the engine's rid): admission, the packed prefill chunks,
and the first token's trip to the host."""
import numpy as np

from program_spans import admissions, first_tokens, recorded


def read(run):
    spans = recorded(run)
    if spans is None:
        return None
    hi = run.trace_window[1]
    first = first_tokens(spans)
    vals = [first[rid] - a for _, a, rid in admissions(spans, *run.trace_window)
            if rid in first and first[rid] <= hi]
    return float(np.mean(vals) / 1e6) if vals else None
