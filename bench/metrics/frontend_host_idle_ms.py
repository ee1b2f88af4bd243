"""Frontend layer (``runtime/frontend.py``): the device's idle time while
a request is outstanding in which the innermost open program span is a
``frontend.*`` span, over the decode steps begun in the traced window, in
milliseconds per step: the frontend's host work between engine calls."""
from program_spans import host_idle_ms


def read(run):
    return host_idle_ms(run, "frontend")
