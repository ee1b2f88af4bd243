"""Dry-run sweep driver: every (arch x shape x mesh) cell as a subprocess
(each needs a fresh jax with the 512-device override), resumable — cells
with an existing JSON are skipped.

  PYTHONPATH=src python -m repro.launch.sweep --out experiments/dryrun \
      [--multi-pod] [--archs a,b] [--shapes s1,s2] [--impl flash]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from repro.configs.registry import ARCH_IDS
from repro.launch.specs import SHAPES, cell_supported


def cell_name(arch, shape, impl, multi_pod):
    pod = "2pod" if multi_pod else "1pod"
    return f"{arch}_{shape}_{impl}_{pod}"


def run_sweep(out_dir, archs, shapes, impl, multi_pod, timeout=1800,
              extra_args=()):
    os.makedirs(out_dir, exist_ok=True)
    results = {}
    for arch in archs:
        for shape in shapes:
            name = cell_name(arch, shape, impl, multi_pod)
            path = os.path.join(out_dir, name + ".json")
            if os.path.exists(path):
                print(f"[skip] {name} (exists)")
                continue
            if not cell_supported(arch, shape):
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape, "skipped": True,
                               "reason": "long_500k needs sub-quadratic attention"},
                              f)
                print(f"[skip] {name} (unsupported cell, recorded)")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--impl", impl,
                   "--out", path]
            if multi_pod:
                cmd.append("--multi-pod")
            cmd.extend(extra_args)
            t0 = time.time()
            print(f"[run ] {name} ...", flush=True)
            env = dict(os.environ)
            env["PYTHONPATH"] = "src"
            env["JAX_PLATFORMS"] = "cpu"   # dry runs compile on host devices
            try:
                p = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=timeout, env=env)
                ok = p.returncode == 0 and os.path.exists(path)
                print(f"[{'ok  ' if ok else 'FAIL'}] {name} "
                      f"({time.time()-t0:.0f}s)", flush=True)
                if not ok:
                    err_path = os.path.join(out_dir, name + ".err")
                    with open(err_path, "w") as f:
                        f.write(p.stdout[-4000:] + "\n--- stderr ---\n"
                                + p.stderr[-8000:])
                    results[name] = "FAIL"
                else:
                    results[name] = "ok"
            except subprocess.TimeoutExpired:
                print(f"[TIME] {name}", flush=True)
                results[name] = "timeout"
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--archs", default=",".join(ARCH_IDS))
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--impl", default="flash")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()
    run_sweep(args.out, args.archs.split(","), args.shapes.split(","),
              args.impl, args.multi_pod, args.timeout)


if __name__ == "__main__":
    main()
