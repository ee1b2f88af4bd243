"""Device layer (the v5e): the share of the time with at least one request
outstanding (due and not yet complete) in which no operation ran on the
chip, inside the traced window, in percent. Waiting for arrivals is not
idle."""
from trace_reduce import clip, covered_ns, intersect_ns


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    off = run.clock_offset_ns + run.t0_ns
    out = []
    for r in run.records:
        end = r.last if r.last is not None else run.drain_end
        s = max(lo, round(off + r.due * 1e9))
        t = min(hi, round(off + end * 1e9))
        if t > s:
            out.append((s, t))
    total = covered_ns(out)
    if not total:
        return None
    busy = clip(run.trace.ops.intervals(), lo, hi)
    return 100.0 * (1.0 - intersect_ns(busy, out) / total)
