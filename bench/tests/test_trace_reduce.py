"""The reduction from a profiler trace to metrics: on intervals worked out
by hand, and on a small profile recorded on a v5e while the serving path
ran (``bench/tests/data/v5e_profile.json``, the reduced form of a
``.xplane.pb`` taken by ``bench/run.py --trace 1``)."""
import os

import pytest

import harness as H
import trace_reduce as TR
from cost import Prefill, Sizes, Step

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "v5e_profile.json")


def ev(name, s, t, dev=0):
    return TR.Event(name, s, t, dev)


def test_union_and_intersection():
    assert TR.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert TR.covered_ns([(0, 2), (1, 3), (5, 9)]) == 7
    assert TR.intersect_ns([(0, 10)], [(2, 3), (8, 12)]) == 3


def test_known_busy_intervals_give_the_idle_share():
    # busy [0,20) [30,40) [35,50) [90,100); requests outstanding over
    # [10, 60): busy inside it 10 + 20 = 30 of 50 -> idle 40 %
    tr = TR.Trace(ops=[ev("a", 0, 20), ev("b", 30, 40), ev("b", 35, 50),
                       ev("c", 90, 100)], modules=[],
                  host=[ev("bench:window", 0, 100, -1)])
    assert TR.busy_ns(tr, 0, 100, 0) == 50
    assert TR.gaps(tr, 0, 100, 0) == [(20, 30), (50, 90)]
    rec = H.Record(H.Request([], 1, 2), due=10e-9)
    rec.last = 60e-9
    run = H.Run(cell=None, sizes=None, peaks={}, records=[rec], window_s=1.0,
                window_tokens=1, setup_s=1.0, drain_end=1.0,
                prefix_before={}, prefix_after={}, trace=tr,
                trace_window=(0, 100), clock_offset_ns=0, t0_ns=0)
    assert H.read_metric("device_idle_share", run) == pytest.approx(40.0)


def test_gaps_are_named_by_the_innermost_host_span():
    tr = TR.Trace(ops=[ev("op", 0, 10), ev("op", 40, 50)], modules=[],
                  host=[ev("bench:pump", 0, 50, -1),
                        ev("bench:engine.audit_state", 15, 35, -1)])
    b = TR.breakdown(tr, 0, 50)
    assert b["idle_gaps"] == [["engine.audit_state", 30e-9]]
    assert b["device_ops"] == [["op", 20e-9]]


def test_kernel_events_are_found_by_name():
    sizes = Sizes(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab_size=512, act="swiglu")
    st = Step({0: 1000}, [1000] * 4, [10] * 4)
    pf = Prefill(1000, 64)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    tr = TR.Trace(
        ops=[ev("packed_bifurcated_decode_attention.13", 0, 1000),
             TR.Event("fusion.3", 1000, 2000, 0,
                      "jit(paged_bifurcated_decode_attention)/pad"),
             TR.Event("custom-call.2", 2000, 3000, 0,
                      "jit(_chunk_body)/jit(paged_bifurcated_decode_attention)"
                      "/pallas_call"),
             ev("fusion.9", 3000, 4000)],
        modules=[], host=[])
    run = H.Run(cell=None, sizes=sizes, peaks=peaks, records=[],
                window_s=1.0, window_tokens=1, setup_s=1.0, drain_end=1.0,
                prefix_before={}, prefix_after={}, trace=tr,
                trace_window=(0, 4000),
                work=H.Work([st, st], [0.0, 0.0], [pf]))
    from cost import window_least_time
    least = window_least_time(sizes, [st, st], [pf], peaks)
    # the first and third events are kernels; the pad fusion is not
    assert H.read_metric("decode_kernel_roofline", run) == pytest.approx(
        100 * least / 2000e-9)
    # the device was busy 4000 ns for two decode steps
    assert H.read_metric("engine_step_ms", run) == pytest.approx(4000e-9 / 2
                                                                 * 1e3)


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded profile")
def test_recorded_v5e_profile():
    tr = TR.load_json(DATA)
    w = TR.host_span(tr, "bench:window")
    assert w is not None
    lo, hi = w.start_ns, w.end_ns
    is_kernel = H.load_metric("decode_kernel_roofline").is_kernel
    kernels = TR.covered_ns(TR.clip(tr.ops.intervals(tr.ops.where(is_kernel)),
                                    lo, hi)) / 1e9
    assert kernels > 0
    busy = TR.mean_busy_s(tr, lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    # the union of device operations covers at least the kernels' time
    assert busy >= kernels * 0.999
    # idle = window - busy, gap by gap
    idle = sum(t - s for s, t in TR.gaps(tr, lo, hi, tr.devices[0]))
    assert idle == pytest.approx((hi - lo) - busy * 1e9, rel=1e-6)


def test_work_is_cut_where_the_trace_ends():
    st = Step({0: 10}, [10], [1])
    work = H.Work([st, st, st], [1.0, 2.0, 3.0],
                  [Prefill(0, 5), Prefill(5, 5), Prefill(10, 5)])
    cut = work.until(2.5, 2)
    assert len(cut.steps) == 2 and cut.prefills == work.prefills[:2]
