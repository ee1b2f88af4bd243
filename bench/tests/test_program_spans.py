"""The readers of the program's own spans (``bench/program_spans.py`` and
the five metrics built on it): on spans and device intervals with answers
worked out by hand, and on a slice of a traced run recorded on a v5e in
the cell ``internlm2.sysprompt_zipf`` (``bench/tests/data/
v5e_program_spans.json``: the program's spans, the device's busy
intervals and the requests' lifetimes)."""
import json
import os
import sys

import pytest

import harness as H
import program_spans as P
import trace_reduce as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "v5e_program_spans.json")
READERS = ("admit_wait_ms", "prefill_ms", "engine_host_idle_ms",
           "frontend_host_idle_ms", "packed_step_share")
OFFSET = 1000          # trace ns = perf_counter ns + OFFSET


def recorder(rows, offset=OFFSET):
    """A recorder holding spans given as (name, sid, parent, attrs, start,
    end) on the trace's clock."""
    from repro.runtime import telemetry

    rec = telemetry.Recorder()
    for name, sid, parent, attrs, s, t in rows:
        rec.append(telemetry.Span(name, sid, parent, dict(attrs),
                                  s - offset, t - offset))
    return rec


def make_run(busy, outstanding, window, offset=OFFSET, drain_end=1.0):
    """A traced run: ``busy`` device intervals, requests outstanding over
    ``outstanding`` (trace ns; the window starts at perf_counter 0)."""
    recs = []
    for due, last in outstanding:
        r = H.Record(H.Request([], 1, 2), due=(due - offset) / 1e9)
        r.last = None if last is None else (last - offset) / 1e9
        recs.append(r)
    tr = TR.Trace(ops=[TR.Event("op", s, t, 0) for s, t in busy],
                  modules=[], host=[])
    return H.Run(cell=None, sizes=None, peaks={}, records=recs, window_s=1.0,
                 window_tokens=1, setup_s=1.0, drain_end=drain_end,
                 prefix_before={}, prefix_after={}, trace=tr,
                 trace_window=window, clock_offset_ns=offset, t0_ns=0)


# one pump: a packed step (dispatch, collect, node write), then a scan of
# three steps and an audit; the device is busy in [0,14) [16,45) [52,55)
# [90,100), a request is outstanding throughout
PUMP = [
    ("frontend.pump", 1, 0, {"round": 1}, 0, 100),
    ("frontend.decode", 2, 1, {}, 10, 60),
    ("engine.packed_step", 3, 2, {"rid": 0, "prefill_tokens": 5,
                                  "decode_rows": 2}, 12, 58),
    ("engine.dispatch", 4, 3, {}, 14, 20),
    ("engine.collect", 5, 3, {}, 20, 50),
    ("engine.node_write", 6, 3, {}, 50, 58),
    ("request.first_token", 7, 3, {"rid": 0}, 58, 58),
    ("frontend.audit", 8, 1, {}, 70, 80),
    ("engine.scan", 9, 1, {"n_steps": 3, "decode_rows": 4}, 85, 88),
]
BUSY = [(0, 14), (16, 45), (52, 55), (90, 100)]


@pytest.fixture
def pump(monkeypatch):
    from repro.runtime import telemetry

    monkeypatch.setattr(telemetry, "RECORDER", recorder(PUMP))
    return make_run(BUSY, [(0, 100)], (0, 100))


def test_idle_goes_to_the_innermost_span(pump):
    split = P.idle_by_span(pump, P.recorded(pump))
    # idle [14,16) dispatch; [45,50) collect; [50,52) [55,58) node write;
    # [58,60) decode; [60,70) [80,85) [88,90) pump; [70,80) audit; [85,88)
    # the scan
    assert split == {"engine.dispatch": 2, "engine.collect": 5,
                     "engine.node_write": 5, "frontend.decode": 2,
                     "frontend.pump": 17, "frontend.audit": 10,
                     "engine.scan": 3, None: 0}
    assert P.decode_steps(P.recorded(pump), 0, 100) == (1, 3)
    assert H.read_metric("engine_host_idle_ms", pump) == pytest.approx(
        15 / 4 / 1e6)
    assert H.read_metric("frontend_host_idle_ms", pump) == pytest.approx(
        29 / 4 / 1e6)
    assert H.read_metric("packed_step_share", pump) == pytest.approx(25.0)


def test_idle_outside_spans_and_requests(monkeypatch):
    from repro.runtime import telemetry

    monkeypatch.setattr(telemetry, "RECORDER", recorder(PUMP))
    # the request is outstanding over [40, 120): idle before 40 does not
    # count; [100,120) lies outside every span
    run = make_run(BUSY, [(40, 120)], (0, 130))
    split = P.idle_by_span(run, P.recorded(run))
    assert split[None] == 20
    assert split["engine.collect"] == 5 and "engine.dispatch" not in split
    assert sum(split.values()) == 5 + 5 + 2 + 17 + 10 + 3 + 20


def test_spans_and_idle_are_clipped_to_the_window(monkeypatch):
    from repro.runtime import telemetry

    rows = PUMP + [("frontend.pump", 10, 0, {"round": 2}, 200, 300),
                   ("engine.scan", 11, 10, {"n_steps": 4}, 210, 290)]
    monkeypatch.setattr(telemetry, "RECORDER", recorder(rows))
    run = make_run(BUSY, [(0, 100)], (30, 80))
    spans = P.recorded(run)
    assert all(s.end >= 30 and s.start <= 80 for s in spans)
    assert "engine.scan" not in {s.name for s in spans}
    split = P.idle_by_span(run, spans)
    # idle in [30,80): [45,50) collect, [50,52) [55,58) node write,
    # [58,60) decode, [60,70) pump, [70,80) audit
    assert split == {"engine.collect": 5, "engine.node_write": 5,
                     "frontend.decode": 2, "frontend.pump": 10,
                     "frontend.audit": 10, None: 0}
    # the packed step began before the window: no decode step in it
    assert H.read_metric("engine_host_idle_ms", run) is None
    assert H.read_metric("packed_step_share", run) is None


def test_request_phases_join_through_the_rid(monkeypatch):
    from repro.runtime import telemetry

    rows = [
        ("frontend.submit", 1, 0, {"tid": 0}, 0, 2),
        ("frontend.submit", 2, 0, {"tid": 1}, 3, 4),
        ("frontend.submit", 3, 0, {"tid": 2}, 5, 6),
        # tid 0's first try fails (no rid), the second admits it
        ("frontend.admit", 4, 0, {"tid": 0}, 10, 11),
        ("frontend.admit", 5, 0, {"tid": 0, "rid": 7}, 20, 30),
        ("frontend.admit", 6, 0, {"tid": 1, "rid": 8}, 25, 26),
        ("request.first_token", 7, 0, {"rid": 7}, 60, 60),
        # past the window's end
        ("request.first_token", 8, 0, {"rid": 8}, 95, 95),
        ("frontend.admit", 9, 0, {"tid": 2, "rid": 9}, 120, 121),
    ]
    monkeypatch.setattr(telemetry, "RECORDER", recorder(rows))
    run = make_run([], [], (0, 90))
    assert P.admissions(P.recorded(run), 0, 90) == [(2, 20, 7), (4, 25, 8)]
    assert H.read_metric("admit_wait_ms", run) == pytest.approx(
        (18 + 21) / 2 / 1e6)
    assert H.read_metric("prefill_ms", run) == pytest.approx(40 / 1e6)


def test_readers_read_nothing_without_spans(monkeypatch):
    from repro.runtime import telemetry

    monkeypatch.setattr(telemetry, "RECORDER", telemetry.Recorder())
    run = make_run(BUSY, [(0, 100)], (0, 100))
    for name in READERS:
        assert H.read_metric(name, run) is None
    # a run without a trace
    monkeypatch.setattr(telemetry, "RECORDER", recorder(PUMP))
    untraced = make_run(BUSY, [(0, 100)], (0, 100))
    untraced.trace, untraced.trace_window = None, None
    for name in READERS:
        assert H.read_metric(name, untraced) is None
    # a program without the recorder
    import repro.runtime

    monkeypatch.delattr(repro.runtime, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.runtime.telemetry", None)
    for name in READERS:
        assert H.read_metric(name, run) is None


# ---------------------------------------------------------------------------
# a slice of a traced run on the v5e
# ---------------------------------------------------------------------------

def load_fixture(path=DATA):
    """(run, recorder) from a recorded slice."""
    with open(path) as f:
        d = json.load(f)
    from repro.runtime import telemetry

    rec = telemetry.Recorder()
    for name, s, t, sid, parent, attrs in d["spans"]:
        rec.append(telemetry.Span(name, sid, parent, attrs, s, t))
    run = make_run([tuple(x) for x in d["busy"]], [], tuple(d["window"]),
                   offset=d["offset"], drain_end=d["drain_end"])
    run.t0_ns = d["t0_ns"]
    for due, last in d["records"]:
        r = H.Record(H.Request([], 1, 2), due=due)
        r.last = last
        run.records.append(r)
    run.trace.modules = [TR.Event(n, s, t, 0) for n, s, t in d["modules"]]
    return d, run, rec


@pytest.fixture
def v5e(monkeypatch):
    from repro.runtime import telemetry

    d, run, rec = load_fixture()
    monkeypatch.setattr(telemetry, "RECORDER", rec)
    return d, run


def test_fixture_is_small():
    assert os.path.getsize(DATA) < 1 << 20


def test_readers_on_the_v5e_slice(v5e):
    d, run = v5e
    for name in READERS:
        want = d["expect"][name]
        got = H.read_metric(name, run)
        assert got is None if want is None else got == pytest.approx(want)
    spans = P.recorded(run)
    split = P.idle_by_span(run, spans)
    total = sum(split.values())
    assert total > 0
    # the device's outstanding idle time lies inside the program's spans
    assert split[None] <= 0.1 * total
    assert H.read_metric("engine_host_idle_ms", run) > 0


def test_packed_step_programs_lie_between_dispatch_and_collect(v5e):
    """Clocks agree to within a millisecond: each run of the packed step's
    program on the device (its ``XLA Modules`` event) begins no more than
    1 ms before the ``engine.dispatch`` span that launched it, and ends
    before the ``engine.collect`` span that waited for it ends. (On the
    v5e the device's events lead the mapped host spans by 0.66-0.78 ms;
    PERF.md, section 6.)"""
    d, run = v5e
    spans = P.recorded(run)
    by_id = {s.sid: s for s in spans}
    lo = run.trace_window[0]

    def under_packed_step(name):
        return [s for s in spans if s.name == name and s.start >= lo
                and by_id.get(s.parent) is not None
                and by_id[s.parent].name == "engine.packed_step"]

    progs = sorted((m for m in run.trace.modules
                    if d["packed_module"] in m.name and m.start_ns >= lo),
                   key=lambda m: m.start_ns)
    dispatch = under_packed_step("engine.dispatch")
    collect = under_packed_step("engine.collect")
    assert progs and len(progs) == len(dispatch) == len(collect)
    for sp, m, c in zip(dispatch, progs, collect):
        assert sp.start - 1_000_000 <= m.start_ns
        assert m.end_ns <= c.end
