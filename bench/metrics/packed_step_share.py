"""Engine layer (``runtime/serve.py``): the share of the decode steps
begun in the traced window that ran as single ``engine.packed_step``
dispatches (one token each, while a prefill was pending) rather than
inside an ``engine.scan``, in percent."""
from program_spans import decode_steps, recorded


def read(run):
    spans = recorded(run)
    if spans is None:
        return None
    packed, scanned = decode_steps(spans, *run.trace_window)
    total = packed + scanned
    return 100.0 * packed / total if total else None
