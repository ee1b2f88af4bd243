"""Spans of the serving path (``runtime/telemetry.py``).

Tracing is on only while a JAX profiler session runs: with the profiler
off the recorder stays empty through ``ServeFrontend.pump``; with it on, a
tiny paged ``TreeServeEngine`` (packed steps, prefix cache, suffix
prefill) served through the frontend records the span tree the serving
path promises — parents, the ticket id and the engine's request id
threaded from ``frontend.submit`` to ``request.first_token``, and packed
steps whose prefill tokens add up to each admission's suffix. Also: the
ring's bound and drop count, a recompile recorded under the span that
caused it, and served tokens bit-identical with and without tracing.
"""
import jax
import numpy as np
import pytest

from repro.configs import TreeConfig, get_config, reduced_config
from repro.models import get_model
from repro.runtime import telemetry
from repro.runtime.frontend import ServeFrontend
from repro.runtime.serve import TreeServeEngine

CFG = reduced_config(get_config("internlm2-1.8b"))
MODEL = get_model(CFG)
PARAMS = MODEL.init(jax.random.PRNGKey(0))
_RNG = np.random.RandomState(0)
SYS = _RNG.randint(0, CFG.vocab_size, (1, 12))
DOC_A = _RNG.randint(0, CFG.vocab_size, (1, 9))
DOC_B = _RNG.randint(0, CFG.vocab_size, (1, 7))
# a fresh two-level path (packed chunks), a path that shares SYS (its
# first try collides with the pending SYS, its suffix is chunked), and a
# full hit (synchronous admission)
WORKLOAD = [[SYS, DOC_A], [SYS, DOC_B], [SYS, DOC_A]]


@pytest.fixture(scope="module")
def engine():
    eng = TreeServeEngine(MODEL, CFG, TreeConfig(
        n_nodes=8, depth=3, slots=6, node_capacity=32, decode_capacity=16,
        temperature=0.0, ctx_store="paged", page_size=8, use_kernel=True,
        step_mode="packed", prefill_chunk=5, prefix_cache=True,
        suffix_prefill=True))
    return eng, eng.host_state()


def serve(engine, trace_dir=None):
    """The workload on the engine reset to its fresh host state; traced
    when ``trace_dir`` is given."""
    eng, fresh = engine
    eng.load_host_state(fresh)
    fe = ServeFrontend(eng)
    st = fe.init_state()
    telemetry.RECORDER.clear()
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    try:
        for segs in WORKLOAD:
            fe.submit(segs, n_samples=2, max_new_tokens=4)
        st = fe.drain(PARAMS, st)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return fe, st, telemetry.RECORDER.snapshot()


@pytest.fixture(scope="module")
def traced(engine, tmp_path_factory):
    serve(engine)                     # compile outside the traced run
    return serve(engine, tmp_path_factory.mktemp("trace"))


def test_recorder_stays_empty_with_profiler_off(engine):
    fe, _, spans = serve(engine)
    assert all(t.status == "completed" for t in fe.tickets)
    assert spans == [] and telemetry.RECORDER.dropped == 0


def test_span_tree_under_the_profiler(traced):
    fe, _, spans = traced
    assert all(t.status == "completed" for t in fe.tickets)
    by_id = {s.sid: s for s in spans}
    assert len(by_id) == len(spans)

    def parent(s):
        return by_id[s.parent].name if s.parent else None

    for s in spans:
        assert s.parent == 0 or s.parent in by_id
        if s.parent:   # a span lies inside the span open around it
            p = by_id[s.parent]
            assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns
    names = {s.name for s in spans}
    assert {"frontend.submit", "frontend.pump", "frontend.collect",
            "frontend.deadlines", "frontend.admit_pass", "frontend.admit",
            "frontend.decode", "frontend.expire", "frontend.quarantine",
            "frontend.watchdog", "frontend.audit", "engine.admit",
            "engine.match", "engine.reserve", "engine.prefill",
            "engine.packed_step", "engine.scan", "engine.inputs",
            "engine.dispatch", "engine.collect", "engine.node_write",
            "engine.activate", "engine.retire", "engine.release",
            "request.first_token"} <= names
    expected_parent = {
        "frontend.submit": {None}, "frontend.pump": {None},
        "frontend.admit_pass": {"frontend.pump"},
        "frontend.admit": {"frontend.admit_pass"},
        "engine.admit": {"frontend.admit"},
        "engine.match": {"engine.admit"},
        "engine.prefill": {"engine.admit"},
        "frontend.decode": {"frontend.pump"},
        "engine.packed_step": {"frontend.decode"},
        "engine.scan": {"frontend.decode"},
        "engine.dispatch": {"engine.packed_step", "engine.scan"},
        "engine.collect": {"engine.packed_step", "engine.scan"},
        "engine.node_write": {"engine.packed_step", "engine.admit"},
        "engine.activate": {"engine.packed_step", "engine.admit"},
        "request.first_token": {"engine.packed_step", "engine.admit"},
    }
    for s in spans:
        if s.name in expected_parent:
            assert parent(s) in expected_parent[s.name], (s, parent(s))

    # tid -> rid -> first token, through frontend.admit
    submitted = {s.attrs["tid"] for s in spans
                 if s.name == "frontend.submit"}
    assert submitted == {t.tid for t in fe.tickets}
    admits = [s for s in spans if s.name == "frontend.admit"]
    ok = {s.attrs["tid"]: s.attrs["rid"] for s in admits if "rid" in s.attrs}
    assert ok == {t.tid: t.handle for t in fe.tickets}
    # the second and third paths first collide with the pending prefill
    assert len(admits) > len(ok)
    firsts = [s for s in spans if s.name == "request.first_token"]
    assert sorted(s.attrs["rid"] for s in firsts) == sorted(ok.values())
    assert all(s.start_ns == s.end_ns for s in firsts)
    engine_admits = {by_id[s.parent].attrs["rid"]: s for s in spans
                     if s.name == "engine.admit" and "rid" in s.attrs}
    for e in engine_admits.values():
        assert by_id[e.parent].attrs["rid"] == e.attrs["rid"]

    # the packed steps' prefill tokens add up to each admission's suffix
    chunked = {}
    for s in spans:
        if s.name == "engine.packed_step":
            chunked[s.attrs["rid"]] = (chunked.get(s.attrs["rid"], 0)
                                       + s.attrs["prefill_tokens"])
            assert 0 <= s.attrs["decode_rows"] <= 6
    new = {rid: e.attrs["new_tokens"] for rid, e in engine_admits.items()}
    assert chunked == {rid: n for rid, n in new.items() if n}
    assert chunked == {ok[0]: 12 + 9, ok[1]: 7}
    assert new[ok[2]] == 0 and engine_admits[ok[2]].attrs[
        "reused_tokens"] == 12 + 9
    # the full hit's first token is sampled at admission
    assert parent(next(s for s in firsts if s.attrs["rid"] == ok[2])) == \
        "engine.admit"
    # every ticket's first tokens reached the host after its submission
    for t in fe.tickets:
        assert t.first_token_wall is not None
        assert t.submit_wall <= t.first_token_wall <= t.finish_wall
    assert fe.metrics()["ttft_s"]["p50"] > 0


def test_ring_bound_and_drop_count(monkeypatch, tmp_path):
    rec = telemetry.Recorder(capacity=4)
    monkeypatch.setattr(telemetry, "RECORDER", rec)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("outer", k=1) as sp:
            for i in range(7):
                with telemetry.span("inner", i=i):
                    pass
            sp.set(done=True)
    finally:
        jax.profiler.stop_trace()
    assert rec.dropped == 4
    kept = rec.snapshot()
    assert [s.name for s in kept] == ["inner"] * 3 + ["outer"]
    assert [s.attrs["i"] for s in kept[:3]] == [4, 5, 6]
    outer = kept[-1]
    assert outer.attrs == {"k": 1, "done": True} and outer.parent == 0
    assert all(s.parent == outer.sid for s in kept[:3])
    with telemetry.span("off") as sp:
        sp.set(ignored=True)
    assert len(rec.snapshot()) == 4 and rec.dropped == 4


def test_recompile_is_recorded_under_its_span(engine, tmp_path):
    eng, fresh = engine
    eng.load_host_state(fresh)
    st = eng.init_state()
    # a scan length no other test steps: the chunk program compiles
    n = eng.tcfg.decode_capacity - 1
    telemetry.RECORDER.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        st = eng.step_chunk(PARAMS, st, n)
    finally:
        jax.profiler.stop_trace()
    spans = telemetry.RECORDER.snapshot()
    by_id = {s.sid: s for s in spans}
    compiles = [s for s in spans if s.name == "compile"]
    assert compiles and all(s.attrs["seconds"] > 0 for s in compiles)
    for c in compiles:
        assert by_id[c.parent].name == "engine.dispatch"
        scan = by_id[by_id[c.parent].parent]
        assert scan.name == "engine.scan" and scan.attrs["n_steps"] == n


def test_tracing_leaves_served_tokens_bit_identical(engine, traced):
    fe_t, _, _ = traced
    fe, _, _ = serve(engine)
    for a, b in zip(fe.tickets, fe_t.tickets):
        assert [x.tolist() for x in a.tokens] == [x.tolist()
                                                  for x in b.tokens]
        assert [x.tobytes() for x in a.logprobs] == [x.tobytes()
                                                     for x in b.logprobs]
