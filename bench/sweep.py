#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: offer its traffic at several fixed
rates, one window each, in one process, and say at which rates the backlog
grew. The highest rate at which it did not is the knee; a cell's
``rate_per_s`` is set to about four fifths of it.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 4 8 12 16

For each rate it prints one JSON line: requests, failed, tokens/s, TTFT
p50/p95, and the requests outstanding (submitted, not complete) averaged
over the window's second and last quarters. The backlog grew where the
last quarter's mean exceeds the second's by more than half, plus two.
Runs only on a TPU.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness as H  # noqa: E402


def outstanding(recs, t):
    return sum(1 for r in recs if r.sent <= t and
               (r.last is None or r.last > t))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    H.jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if H.jax.devices()[0].platform != "tpu":
        sys.exit("sweep: JAX found no TPU")
    cell = H.load_cell(args.workload)
    clock = H.CompileClock()
    srv = H.Server(cell, args.seed)
    windows = []
    for rate in args.rates:
        cell.mix["rate_per_s"] = rate
        tr = H.Traffic(cell.mix, srv.cfg.vocab_size, args.seed)
        windows.append(tr.window(args.seconds))
    split = H.warm_up(srv, tr, [r for w in windows for r in w], clock)
    print(json.dumps({"warm_up": split}), flush=True)
    for rate, reqs in zip(args.rates, windows):
        recs, window_s, tokens, late, t0, _ = H.run_window(srv, reqs,
                                                           args.seconds)
        end = H.drain_records(srv, recs, t0, window_s + H.DRAIN_S)
        q2 = [outstanding(recs, args.seconds * (0.25 + 0.25 * i / 8))
              for i in range(8)]
        q4 = [outstanding(recs, args.seconds * (0.75 + 0.25 * i / 8))
              for i in range(8)]
        ttft = sorted((r.first if r.first is not None else end) - r.due
                      for r in recs)
        m2, m4 = sum(q2) / 8, sum(q4) / 8
        print(json.dumps({
            "rate_per_s": rate, "requests": len(recs),
            "offered_tokens_per_s": sum(r.total_tokens for r in recs)
            / args.seconds,
            "failed": sum(r.failed for r in recs),
            "tokens_per_s": tokens / window_s,
            "ttft_p50_ms": 1e3 * ttft[len(ttft) // 2],
            "ttft_p95_ms": 1e3 * ttft[int(0.95 * (len(ttft) - 1))],
            "outstanding_q2": m2, "outstanding_q4": m4,
            "backlog_grew": m4 > 1.5 * m2 + 2,
            "generator_late_max_ms": 1e3 * max(late or [0.0]),
            "compiles": clock.count}), flush=True)
        time.sleep(1.0)


if __name__ == "__main__":
    main()
