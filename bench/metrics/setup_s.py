"""Seconds from process start to the start of the window: imports, device
start, weights, compiles (or cache loads), and the warm-up traffic."""


def read(run):
    return run.setup_s
