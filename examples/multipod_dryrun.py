"""Multi-pod dry-run demo: lower + compile one cell on the 2x16x16 mesh
(512 placeholder devices) and print its roofline terms.

  PYTHONPATH=src python examples/multipod_dryrun.py [--arch internlm2-1.8b] \
      [--shape decode_32k]

This is a thin wrapper over repro.launch.dryrun (which owns the mandatory
XLA_FLAGS device-count override); see launch/sweep.py for the full 40-cell
matrix.
"""
import argparse
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--shape", default="decode_32k")
    args = ap.parse_args()
    # dry runs compile on 512 host devices; JAX_PLATFORMS=cpu keeps the
    # children off any chip
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    for flag in ([], ["--multi-pod"]):
        print(f"=== {'multi-pod (2x16x16)' if flag else 'single-pod (16x16)'} ===")
        subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", args.arch, "--shape", args.shape] + flag,
            check=True, env=env, cwd=REPO)


if __name__ == "__main__":
    main()
