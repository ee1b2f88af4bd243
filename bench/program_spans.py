"""The program's own spans (``repro.runtime.telemetry``) placed on the
profiler trace's clock, for the readers in ``bench/metrics`` that put a
request's latency and the device's idle time down to frontend and engine
phases.

The program records a span while a profiler session runs, with its times
on ``time.perf_counter_ns``; ``run.clock_offset_ns`` maps them onto the
trace (trace ns = perf_counter ns + offset). A program without the
recorder, or a run without a trace, gives ``None`` and every reader then
reads nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from trace_reduce import _union, clip


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int            # trace ns
    end: int
    sid: int = 0
    parent: int = 0
    attrs: dict = dataclasses.field(default_factory=dict)


def recorded(run) -> Optional[List[Span]]:
    """The program's spans that overlap the traced window, on the trace's
    clock, in order of start; ``None`` where there are none."""
    if getattr(run, "trace", None) is None or run.trace_window is None:
        return None
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    lo, hi = run.trace_window
    off = run.clock_offset_ns
    out = [Span(s.name, s.start_ns + off, s.end_ns + off, s.sid, s.parent,
                dict(s.attrs))
           for s in telemetry.RECORDER.snapshot()]
    out = sorted((s for s in out if s.end >= lo and s.start <= hi),
                 key=lambda s: (s.start, -s.end))
    return out or None


def _starts_in(spans, name, lo, hi):
    return [s for s in spans if s.name == name and lo <= s.start < hi]


def decode_steps(spans: List[Span], lo: int, hi: int):
    """(single packed steps, steps run in scans) begun in [lo, hi)."""
    packed = len(_starts_in(spans, "engine.packed_step", lo, hi))
    scanned = sum(int(s.attrs.get("n_steps", 0))
                  for s in _starts_in(spans, "engine.scan", lo, hi))
    return packed, scanned


# ---------------------------------------------------------------------------
# request phases
# ---------------------------------------------------------------------------

def admissions(spans: List[Span], lo: int, hi: int) -> List[tuple]:
    """For each ticket submitted and first admitted in [lo, hi]: (end of
    ``frontend.submit``, start of its first ``frontend.admit`` that
    succeeded, the engine's rid)."""
    submitted = {s.attrs["tid"]: s.end for s in spans
                 if s.name == "frontend.submit" and "tid" in s.attrs}
    admitted: Dict[int, Span] = {}
    for s in spans:
        if s.name == "frontend.admit" and "rid" in s.attrs:
            admitted.setdefault(s.attrs["tid"], s)
    out = []
    for tid, sub_end in submitted.items():
        a = admitted.get(tid)
        if a is not None and lo <= sub_end and a.start <= hi:
            out.append((sub_end, a.start, a.attrs["rid"]))
    return out


def first_tokens(spans: List[Span]) -> Dict[int, int]:
    """rid -> when ``request.first_token`` was recorded."""
    out = {}
    for s in spans:
        if s.name == "request.first_token":
            out.setdefault(s.attrs["rid"], s.start)
    return out


# ---------------------------------------------------------------------------
# idle time put down to the innermost span
# ---------------------------------------------------------------------------

def innermost(spans: List[Span]):
    """The time the spans cover, cut where the innermost open span
    changes: (starts, ends, names) of disjoint, sorted segments. Spans
    nest (one thread, one stack); zero-length events cover nothing."""
    starts, ends, names = [], [], []
    stack: List[Span] = []
    cursor = None

    def emit(upto):
        if stack and cursor is not None and upto > cursor:
            starts.append(cursor)
            ends.append(upto)
            names.append(stack[-1].name)

    for s in spans:
        if s.end <= s.start:
            continue
        while stack and stack[-1].end <= s.start:
            emit(stack[-1].end)
            cursor = stack.pop().end
        emit(s.start)
        cursor = s.start
        stack.append(s)
    while stack:
        emit(stack[-1].end)
        cursor = stack.pop().end
    return (np.asarray(starts, np.int64), np.asarray(ends, np.int64),
            names)


def outstanding(run) -> np.ndarray:
    """Merged intervals, on the trace's clock and inside the traced window,
    in which at least one request was due and not complete (as
    ``device_idle_share`` defines them)."""
    lo, hi = run.trace_window
    off = run.clock_offset_ns + run.t0_ns
    out = []
    for r in run.records:
        end = r.last if r.last is not None else run.drain_end
        s = max(lo, round(off + r.due * 1e9))
        t = min(hi, round(off + end * 1e9))
        if t > s:
            out.append((s, t))
    return _union(np.asarray(out, np.int64).reshape(-1, 2))


def idle_outstanding(run) -> np.ndarray:
    """Merged intervals in which a request was outstanding and no
    operation ran on the device."""
    lo, hi = run.trace_window
    busy = _union(clip(run.trace.ops.intervals(), lo, hi))
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    out = [clip(gaps, int(s), int(t)) for s, t in outstanding(run)]
    return (np.concatenate(out) if out
            else np.zeros((0, 2), np.int64))


def _covered_before(iv: np.ndarray, t: np.ndarray) -> np.ndarray:
    """For sorted disjoint intervals ``iv``: their length before each
    time in ``t``."""
    if not len(iv):
        return np.zeros(len(t), np.int64)
    lens = iv[:, 1] - iv[:, 0]
    cum = np.concatenate([[0], np.cumsum(lens)])
    k = np.searchsorted(iv[:, 0], t, side="right")    # intervals begun
    last = np.maximum(k - 1, 0)
    part = np.clip(t - iv[last, 0], 0, lens[last])
    return np.where(k > 0, cum[last] + part, 0)


def idle_by_span(run, spans: List[Span]) -> Dict[str, int]:
    """Outstanding idle ns by the name of the innermost program span open
    over it; what no span covers is under ``None``."""
    idle = idle_outstanding(run)
    total = int((idle[:, 1] - idle[:, 0]).sum()) if len(idle) else 0
    starts, ends, names = innermost(spans)
    per = (_covered_before(idle, ends) - _covered_before(idle, starts)
           if len(starts) else np.zeros(0, np.int64))
    out: Dict[str, int] = {}
    for name, ns in zip(names, per.tolist()):
        if ns:
            out[name] = out.get(name, 0) + int(ns)
    out[None] = total - sum(out.values())
    return out


def host_idle_ms(run, layer: str) -> Optional[float]:
    """Outstanding idle time whose innermost program span is
    ``<layer>.*``, per decode step begun in the traced window, in ms."""
    spans = recorded(run)
    if spans is None:
        return None
    lo, hi = run.trace_window
    steps = sum(decode_steps(spans, lo, hi))
    if not steps:
        return None
    split = idle_by_span(run, spans)
    ns = sum(v for k, v in split.items()
             if k is not None and k.startswith(layer + "."))
    return ns / steps / 1e6
