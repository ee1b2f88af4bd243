"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only name]

| module          | paper artifact                                        |
|-----------------|-------------------------------------------------------|
| memory_io       | Table 1/6 + abstract 2.1x/6.2x claims (IO model)      |
| latency_decode  | Table 1 analog, measured on CPU proxy                 |
| batch_scaling   | Figure 6 (latency vs context, per batch)              |
| mh_vs_mq        | Figure 5 / Figure 7 (capability-equalized MH vs MQ)   |
| scaling_laws    | Figure 3 (loss vs size for g = h / 2 / 1), trained    |
| kernel_io       | Appendix H (kernel comparison), Pallas vs einsums     |
| tensor_parallel | Table 8 (bifurcation under TP, 8-device compiles)     |
| pass_at_k       | Figure 8 / §5.4 (pass@n, pass@top3 via mean logprob)  |
| serve_soak      | robustness soak (frontend + faults, oversubscribed)   |
| roofline_table  | deliverable (g): dry-run roofline aggregation         |

Prints ``name,us_per_call,derived`` CSV rows via report().
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = [
    "memory_io",
    "latency_decode",
    "batch_scaling",
    "mh_vs_mq",
    "kernel_io",
    "tensor_parallel",
    "pass_at_k",
    "serve_soak",
    "scaling_laws",
    "roofline_table",
]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    mods = [args.only] if args.only else MODULES

    rows = []

    def report(name, value):
        rows.append((name, value))
        print(f"{name},{value}")

    failures = []
    for name in mods:
        print(f"# === benchmarks.{name} ===", flush=True)
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run(report)
            print(f"# {name} ok ({time.perf_counter()-t0:.1f}s)", flush=True)
        except Exception:  # noqa: BLE001 — report all, fail at end
            failures.append(name)
            traceback.print_exc()
            print(f"# {name} FAILED", flush=True)
    print(f"# done: {len(rows)} metrics, failures: {failures or 'none'}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
