#!/usr/bin/env python3
"""Readings behind a cell's check limit: for each seed, one run of the cell
at its own size and load (a short window), and on the same sample of
served requests both the program's readings (mean and widest logit gap,
largest log-prob error) and the int8 control's (the reference with int8 weights
and activations put in the program's place). One process for all seeds.

    python3 bench/calibrate.py --workload <name> --seconds <s> \
        --seeds 1 2 3

Prints one JSON line per seed, with ``correct`` (the program's verdict)
and ``control_correct`` (the control's readings put through the same
verdict), and a last line with, for each number, the lower reading (the
largest the program gives) and the upper one (the smallest the control
gives). Exits non-zero when the control passes the check on any seed, or
the program fails it. Runs only on a TPU; the benchmark's own runs never
run the control.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness as H  # noqa: E402
import run as R  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    H.jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = H.jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("calibrate: JAX found no TPU")
    peaks = H.load_peaks(dev.device_kind)
    cell = H.load_cell(args.workload)
    program, ctl, verdicts = {}, {}, []
    for seed in args.seeds:
        t = time.time()
        out = R.run_cell(cell, seed, args.seconds, False, peaks, t,
                         control=True)
        line = {"seed": seed, "correct": out["correct"],
                "control_correct": out["control"]["correct"],
                "attempted": out["attempted"], "failed": out["failed"],
                "seconds": time.time() - t}
        verdicts.append((out["correct"], out["control"]["correct"]))
        for k, v in out["check"].items():
            line[k] = v["value"]
            program.setdefault(k, []).append(v["value"])
        for k, v in out["readings"].items():
            line[k] = v
            program.setdefault(k, []).append(v)
        for k, v in out["control"].items():
            if k == "correct":
                continue
            line["control_" + k] = v
            ctl.setdefault(k, []).append(v)
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "lower": {k: max(v) for k, v in program.items()},
        "upper": {k: min(v) for k, v in ctl.items()},
        "program": program, "control": ctl,
        "program_correct": [p for p, _ in verdicts],
        "control_correct": [c for _, c in verdicts]}), flush=True)
    if any(c for _, c in verdicts) or not all(p for p, _ in verdicts):
        sys.exit("calibrate: the control passed the check, or the program "
                 "failed it")


if __name__ == "__main__":
    main()
