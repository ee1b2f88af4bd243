"""Engine layer (``runtime/serve.py``): the device's busy time in the
traced window (every operation the engine ran: steps, node writes, slot
updates) over the decode steps that the frontend asked the engine for in
it, in milliseconds per step."""
from trace_reduce import mean_busy_s


def read(run):
    if run.trace is None or not run.work.steps:
        return None
    busy = mean_busy_s(run.trace, *run.trace_window)
    return busy / len(run.work.steps) * 1e3 if busy else None
