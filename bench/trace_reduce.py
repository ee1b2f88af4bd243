"""Reduce a profiler trace to what the per-layer metrics read: the device's
busy intervals, each operation's and each kernel's device time, and the
host spans the benchmark wrote around its calls into the program.

A trace is read from the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` and nothing else. Device planes are those
named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation (a Pallas kernel is one operation, named after its kernel
function) and the ``XLA Modules`` line one event per run of a compiled
program (named after the jitted function). Host spans are the events whose
names start with ``bench:``. A window of serving holds millions of
operations, so they are kept in columns (``Ops``) and reduced with numpy.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from array import array
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench:"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: int
    end_ns: int
    device: int = -1          # chip index; -1 for host spans
    info: str = ""            # the op's framework path (``tf_op``) etc.

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


class Ops:
    """Device operations in columns: for event k, ``names[name[k]]`` with
    ``infos[name[k]]`` (its framework path, read once per name), the
    interval ``[start[k], end[k])`` in ns on chip ``device[k]``."""

    def __init__(self, names, infos, name, start, end, device):
        self.names, self.infos = list(names), list(infos)
        self.name = np.asarray(name, np.int64)
        self.start = np.asarray(start, np.int64)
        self.end = np.asarray(end, np.int64)
        self.device = np.asarray(device, np.int64)

    @classmethod
    def from_events(cls, events: Sequence[Event]) -> "Ops":
        index: Dict[str, int] = {}
        names, infos = [], []
        for e in events:
            if e.name not in index:
                index[e.name] = len(names)
                names.append(e.name)
                infos.append(e.info)
        return cls(names, infos, [index[e.name] for e in events],
                   [e.start_ns for e in events], [e.end_ns for e in events],
                   [e.device for e in events])

    def __len__(self) -> int:
        return len(self.start)

    def where(self, keep: Callable[[str, str], bool]) -> np.ndarray:
        """Mask of the events whose (name, info) ``keep`` accepts."""
        ok = np.array([bool(keep(n, i)) for n, i in zip(self.names,
                                                         self.infos)],
                      dtype=bool)
        return ok[self.name] if len(ok) else np.zeros(0, bool)

    def intervals(self, mask=None, device: Optional[int] = None) -> np.ndarray:
        """(n, 2) intervals of the events in ``mask`` (all by default), on
        one chip or every chip."""
        m = np.ones(len(self), bool) if mask is None else mask
        if device is not None:
            m = m & (self.device == device)
        return np.stack([self.start[m], self.end[m]], axis=1)


@dataclasses.dataclass
class Trace:
    ops: Ops                  # device operations, every chip
    modules: List[Event]      # device program runs, every chip
    host: List[Event]         # the benchmark's host spans

    def __post_init__(self):
        if not isinstance(self.ops, Ops):
            self.ops = Ops.from_events(self.ops)

    @property
    def devices(self) -> List[int]:
        return sorted(set(self.ops.device.tolist())
                      | {e.device for e in self.modules})

    def to_json(self) -> dict:
        def enc(evs):
            return [[e.name, e.start_ns, e.end_ns, e.device, e.info]
                    for e in evs]
        o = self.ops
        return {"op_names": o.names, "op_infos": o.infos,
                "ops": np.stack([o.name, o.start, o.end, o.device],
                                axis=1).tolist(),
                "modules": enc(self.modules), "host": enc(self.host)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        def dec(rows):
            return [Event(r[0], int(r[1]), int(r[2]), int(r[3]),
                          r[4] if len(r) > 4 else "") for r in rows]
        cols = np.asarray(d["ops"], np.int64).reshape(-1, 4)
        ops = Ops(d["op_names"], d["op_infos"], cols[:, 0], cols[:, 1],
                  cols[:, 2], cols[:, 3])
        return cls(ops, dec(d["modules"]), dec(d["host"]))


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


INFO_STATS = ("tf_op", "long_name", "hlo_category")


def _info(ev) -> str:
    try:
        stats = dict(ev.stats or {})
    except (TypeError, ValueError):
        return ""
    return " ".join(str(stats[k]) for k in INFO_STATS if k in stats)


def op_name(raw: str) -> str:
    """An op event's name: on the TPU the event carries the HLO text
    (``%packed_bifurcated_decode_attention.13 = bf16[...] custom-call(...)``);
    keep the instruction name."""
    return raw.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Read a ``.xplane.pb`` (or a directory holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    by_raw: Dict[str, int] = {}
    by_name: Dict[str, int] = {}
    names: List[str] = []
    infos: List[str] = []
    nid, start, end, dev = array("q"), array("q"), array("q"), array("q")
    modules, host = [], []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if not m:
                host.extend(Event(ev.name, int(ev.start_ns), int(ev.end_ns))
                            for ev in line.events
                            if ev.name.startswith(HOST_PREFIX))
                continue
            d = int(m.group(1))
            if line.name == MODULES_LINE:
                modules.extend(Event(ev.name, int(ev.start_ns),
                                     int(ev.end_ns), d, "")
                               for ev in line.events)
            elif line.name == OPS_LINE:
                for ev in line.events:
                    raw = ev.name
                    k = by_raw.get(raw)
                    if k is None:
                        name = op_name(raw)
                        k = by_name.get(name)
                        if k is None:
                            k = by_name[name] = len(names)
                            names.append(name)
                            infos.append(_info(ev))
                        by_raw[raw] = k
                    nid.append(k)
                    start.append(int(ev.start_ns))
                    end.append(int(ev.end_ns))
                    dev.append(d)
    ops = Ops(names, infos, np.frombuffer(nid, np.int64),
              np.frombuffer(start, np.int64), np.frombuffer(end, np.int64),
              np.frombuffer(dev, np.int64))
    return Trace(ops, modules, host)


def _as_array(intervals) -> np.ndarray:
    if isinstance(intervals, np.ndarray):
        return intervals.reshape(-1, 2).astype(np.int64)
    return np.asarray(list(intervals), np.int64).reshape(-1, 2)


def clip(events, lo: int, hi: int) -> np.ndarray:
    """Intervals cut to [lo, hi], empty ones dropped; ``events`` are
    ``Event``s, or intervals as pairs or an (n, 2) array."""
    if not isinstance(events, np.ndarray):
        events = [(e.start_ns, e.end_ns) if isinstance(e, Event) else e
                  for e in events]
    a = _as_array(events)
    a = np.stack([np.maximum(a[:, 0], lo), np.minimum(a[:, 1], hi)], axis=1)
    return a[a[:, 1] > a[:, 0]]


def _union(a: np.ndarray) -> np.ndarray:
    """Merged intervals of an (n, 2) array, sorted."""
    a = _as_array(a)
    if not len(a):
        return a
    a = a[np.argsort(a[:, 0], kind="stable")]
    reach = np.maximum.accumulate(a[:, 1])
    new = np.ones(len(a), bool)
    new[1:] = a[1:, 0] > reach[:-1]
    first = np.nonzero(new)[0]
    last = np.append(first[1:] - 1, len(a) - 1)
    return np.stack([a[first, 0], reach[last]], axis=1)


def union(intervals) -> List[Tuple[int, int]]:
    """Merge overlapping intervals."""
    return [(int(s), int(t)) for s, t in _union(_as_array(intervals))]


def covered_ns(intervals) -> int:
    u = _union(_as_array(intervals))
    return int((u[:, 1] - u[:, 0]).sum()) if len(u) else 0


def intersect_ns(a, b) -> int:
    """Length of the intersection of two interval sets."""
    a, b = _union(_as_array(a)), _union(_as_array(b))
    tot = 0
    for s, t in b:
        c = clip(a, int(s), int(t))
        tot += int((c[:, 1] - c[:, 0]).sum())
    return tot


def busy_ns(trace: Trace, lo: int, hi: int, device: int) -> int:
    """Time in [lo, hi] in which some operation ran on ``device``."""
    return covered_ns(clip(trace.ops.intervals(device=device), lo, hi))


def mean_busy_s(trace: Trace, lo: int, hi: int) -> float:
    devs = sorted(set(trace.ops.device.tolist())) or [0]
    return sum(busy_ns(trace, lo, hi, d) for d in devs) / len(devs) / 1e9


def gaps(trace: Trace, lo: int, hi: int, device: int) -> List[Tuple[int, int]]:
    """Idle intervals of ``device`` inside [lo, hi]."""
    u = _union(clip(trace.ops.intervals(device=device), lo, hi))
    edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
    edges = edges[edges[:, 1] > edges[:, 0]]
    return [(int(s), int(t)) for s, t in edges]


def op_time_by_name(ops: Ops, lo: int, hi: int) -> Dict[str, float]:
    """Device seconds per operation name inside [lo, hi], every chip."""
    s, t = np.maximum(ops.start, lo), np.minimum(ops.end, hi)
    dur = np.where(t > s, t - s, 0).astype(np.float64)
    tot = np.bincount(ops.name, weights=dur, minlength=len(ops.names))
    return {ops.names[k]: float(tot[k]) / 1e9 for k in np.nonzero(tot)[0]}


def last_op_end(trace: Trace) -> Optional[int]:
    """When the last device operation the trace kept ended, if any."""
    return int(trace.ops.end.max()) if len(trace.ops) else None


def host_span(trace: Trace, name: str) -> Optional[Event]:
    """The longest host span of that name."""
    evs = [e for e in trace.host if e.name == name]
    return max(evs, key=lambda e: e.dur_ns) if evs else None


def innermost_host_span(trace: Trace, t_ns: int) -> Optional[Event]:
    """The shortest benchmark host span that covers ``t_ns``."""
    cover = [e for e in trace.host if e.start_ns <= t_ns < e.end_ns]
    return min(cover, key=lambda e: e.dur_ns) if cover else None


def breakdown(trace: Trace, lo: int, hi: int, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle gaps
    named by what the host was doing in their middle."""
    by_op = op_time_by_name(trace.ops, lo, hi)
    devs = sorted(set(trace.ops.device.tolist())) or [0]
    n = len(devs)
    ops = sorted(((k, v / n) for k, v in by_op.items()),
                 key=lambda kv: -kv[1])[:top]
    longest = sorted(gaps(trace, lo, hi, devs[0]),
                     key=lambda g: g[0] - g[1])[:top]
    idle = []
    for s, t in longest:
        span = innermost_host_span(trace, (s + t) // 2)
        idle.append([span.name[len(HOST_PREFIX):] if span else "unattributed",
                     (t - s) / 1e9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}


def save_json(trace: Trace, path: str):
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
