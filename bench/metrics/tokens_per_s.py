"""Output tokens of every sample that reached the host inside the window,
over the window's seconds."""


def read(run):
    return run.window_tokens / run.window_s if run.window_s > 0 else None
