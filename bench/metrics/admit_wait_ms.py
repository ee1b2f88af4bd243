"""Frontend layer (``runtime/frontend.py``): mean over the tickets
submitted and admitted in the traced window of the time from the end of
their ``frontend.submit`` span to the start of the first
``frontend.admit`` span that admitted them (the program's spans, on the
host clock): the queue, without the rest of the pump."""
import numpy as np

from program_spans import admissions, recorded


def read(run):
    spans = recorded(run)
    if spans is None:
        return None
    adm = admissions(spans, *run.trace_window)
    return float(np.mean([a - s for s, a, _ in adm]) / 1e6) if adm else None
