"""Engine layer (``runtime/serve.py``): the device's idle time while a
request is outstanding (as ``device_idle_share`` defines it) in which the
innermost open program span is an ``engine.*`` span, over the decode
steps begun in the traced window (one per ``engine.packed_step``,
``n_steps`` per ``engine.scan``), in milliseconds per step: the host work
of the engine that holds the chip."""
from program_spans import host_idle_ms


def read(run):
    return host_idle_ms(run, "engine")
