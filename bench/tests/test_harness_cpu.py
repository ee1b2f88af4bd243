"""CPU rehearsal of the harness: a cell end to end at ``reduced_config``
widths (interpret-mode kernels), the arithmetic of the end-to-end metrics,
and the check's verdict on a sound run and on runs with the timed path
broken underneath (``run_cell`` skips only the look for a chip)."""
import dataclasses
import json
import time

import numpy as np
import pytest

import harness as H
import run as R
from conftest import tiny_cell
from loadgen import Request

KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]


def rehearse(workload, peaks, seconds=3.0, seed=2**31 + 5, **kw):
    cell, cfg = tiny_cell(workload)
    H.MIN_COMPARED = 8
    return R.run_cell(cell, seed, seconds, False, peaks, time.time(),
                      cfg=cfg, **kw)


@pytest.mark.parametrize("workload", ["internlm2.sysprompt_zipf"])
def test_cell_end_to_end(workload, peaks, capsys):
    out = rehearse(workload, peaks)
    R.emit(dict(out))
    line = capsys.readouterr().out.strip().splitlines()[-1]
    got = json.loads(line)
    assert list(got)[-1] == "check"
    for k in KEYS:
        assert k in got
    assert got["correct"] is True
    assert got["attempted"] > 0 and got["failed"] == 0
    cell = H.make_cell(workload, *workload.split("."))
    assert set(got["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in got["metrics"].values())


def test_control_goes_through_the_same_verdict(peaks):
    """The int8 control's readings, on the same sample, go through the
    verdict that decides ``correct`` (at these widths they pass it; at six
    layers of the published widths they fail it: ``test_control.py``)."""
    cell, _cfg = tiny_cell("internlm2.sysprompt_zipf")
    out = rehearse("internlm2.sysprompt_zipf", peaks, control=True)
    ctl = out["control"]
    assert out["correct"] is True
    assert ctl["mean_logit_gap"] >= out["check"]["mean_logit_gap"]["value"]
    assert ctl["correct"] is H.verdict(
        cell, ctl["mean_logit_gap"], out["check"]["tokens_compared"]["value"])


def _run(records, **kw):
    base = dict(cell=None, sizes=None, peaks={}, records=records,
                window_s=10.0, window_tokens=500, setup_s=3.5, drain_end=70.0,
                prefix_before={"reused_tokens": 10, "new_tokens": 5},
                prefix_after={"reused_tokens": 40, "new_tokens": 15})
    base.update(kw)
    return H.Run(**base)


def _rec(due, admitted, first, last, new, failed=False):
    r = H.Record(Request([np.zeros(4, np.int32)], 2, new), due=due)
    r.admitted, r.first, r.last, r.failed = admitted, first, last, failed
    return r


def test_end_to_end_arithmetic():
    recs = [_rec(0.0, 0.1, 0.5, 1.5, 11), _rec(1.0, 1.2, 1.4, 2.4, 6),
            _rec(2.0, None, None, None, 5, failed=True)]
    run = _run(recs)
    ttft = [0.5, 0.4, 68.0]
    assert H.read_metric("ttft_p95_ms", run) == pytest.approx(
        np.percentile(ttft, 95) * 1e3)
    assert H.read_metric("tpot_p95_ms", run) == pytest.approx(
        np.percentile([0.1, 0.2], 95) * 1e3)
    assert H.read_metric("tokens_per_s", run) == pytest.approx(50.0)
    assert H.read_metric("setup_s", run) == 3.5
    assert H.read_metric("queue_wait_ms", run) == pytest.approx(150.0)
    assert H.read_metric("prefix_reuse_share", run) == pytest.approx(75.0)
    # device metrics read nothing without a trace
    for name in ("engine_step_ms", "decode_kernel_roofline", "step_mfu",
                 "device_idle_share"):
        assert H.read_metric(name, run) is None


def test_token_altered_where_produced_is_caught(peaks, monkeypatch):
    from repro.runtime.serve import _SlotTableEngine

    orig = _SlotTableEngine._collect_emitted

    def altered(self, toks, lps, emits):
        return orig(self, (np.asarray(toks) + 1) % self.cfg.vocab_size, lps,
                    emits)

    monkeypatch.setattr(_SlotTableEngine, "_collect_emitted", altered)
    out = rehearse("internlm2.sysprompt_zipf", peaks)
    assert out["correct"] is False
    assert out["check"]["mean_logit_gap"]["value"] > \
        out["check"]["mean_logit_gap"]["limit"]


def test_step_that_leaves_the_model_state_unchanged_is_caught(peaks,
                                                              monkeypatch):
    from repro.runtime.serve import _SlotTableEngine

    orig = _SlotTableEngine._decode_one

    def frozen(self, params, state):
        new, out = orig(self, params, state)
        # the step counters move on, the KV cache and the last token do not
        return dataclasses.replace(state, steps=new.steps, active=new.active,
                                   key=new.key), out

    monkeypatch.setattr(_SlotTableEngine, "_decode_one", frozen)
    out = rehearse("internlm2.sysprompt_zipf", peaks)
    assert out["correct"] is False
