#!/usr/bin/env python3
"""Time one layer's paged and packed decode-attention call on a TPU at
the serving benchmark's shapes, with a chosen number of live pages.

    python3 tools/walk_microbench.py [--n-live 0 64 240] [--calls 200]

Shapes are ``bench/traffic/sysprompt_zipf.json``'s engine on
internlm2-1.8b: 8 kv heads of 128 with 2 query heads each, 64 slots of 64
decode tokens, 32 trie nodes of 16 pages of 128 tokens (a 512-entry page
list), a 240-page bfloat16 pool, depth 2; the packed call adds a
128-token prefill chunk in a 16-tile fresh envelope. For each live-page
count it prints one JSON line: milliseconds per call (mean over
``--calls`` back-to-back calls after a warm-up, host clock around
``block_until_ready``), the kernel's grid and the microseconds per grid
step. The difference between two live-page counts over the difference in
live grid steps is the cost of a live step; the time at 0 live pages over
the grid is the cost of a dead one.

It runs only on a TPU: on any other backend it exits non-zero.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops  # noqa: E402

G, P_Q, HD = 8, 2, 128
SLOTS, C_D = 64, 64
N_NODES, PPN, PM, POOL = 32, 16, 128, 240
DEPTH, CHUNK = 2, 128


def _walk_grid(packed: bool):
    """The kernel's grid at these shapes, as the program chooses it."""
    kw = dict(b=SLOTS, g=G, p=P_Q, n=1, hd=HD, c_d=C_D, pm=PM,
              envelope=N_NODES * PPN, depth=DEPTH, itemsize=2)
    if packed:
        return ops.packed_walk_grid(**kw, chunk=CHUNK, fresh_tiles=PPN)
    return ops.paged_walk_grid(**kw)


def _inputs(n_live: int, rng):
    bf = jnp.bfloat16
    tables = np.full((N_NODES, PPN), -1, np.int32)
    lens = np.zeros((N_NODES,), np.int32)
    ids = rng.permutation(POOL)
    used = 0
    for s in range(N_NODES):
        k = min(PPN, n_live - used)
        if k <= 0:
            break
        tables[s, :k] = ids[used:used + k]
        lens[s] = k * PM
        used += k
    n_seg = max(1, int((lens > 0).sum()))
    paths = np.full((DEPTH, SLOTS), -1, np.int32)
    paths[0] = np.arange(SLOTS) % n_seg
    if n_seg > 1:
        paths[1] = (np.arange(SLOTS) + 1) % n_seg
    rnd = lambda *s: jnp.asarray(rng.standard_normal(s, np.float32), bf)
    return dict(
        q=rnd(SLOTS, G, P_Q, 1, HD),
        k_pages=rnd(POOL, G, PM, HD), v_pages=rnd(POOL, G, PM, HD),
        page_tables=jnp.asarray(tables), seg_lens=jnp.asarray(lens),
        paths=jnp.asarray(paths),
        k_dec=rnd(SLOTS, C_D, G, HD), v_dec=rnd(SLOTS, C_D, G, HD),
        dec_mask=jnp.arange(C_D)[None, :] < jnp.asarray(
            rng.integers(1, C_D + 1, SLOTS))[:, None],
        chunk=dict(
            q_fresh=rnd(CHUNK, G, P_Q, HD),
            k_fresh=rnd(PPN * PM, G, HD), v_fresh=rnd(PPN * PM, G, HD),
            fresh_len=jnp.int32(CHUNK), fresh_start=jnp.int32(2048),
            fresh_pos=jnp.arange(2048, 2048 + CHUNK, dtype=jnp.int32),
            fresh_path=jnp.asarray([0] + [-1] * (DEPTH - 1), jnp.int32)),
    )


def _time(fn, calls: int) -> float:
    jax.block_until_ready(fn())
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = None
    for _ in range(calls):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n-live", type=int, nargs="+", default=[0, 64, 240])
    ap.add_argument("--calls", type=int, default=200)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"walk_microbench: no TPU (backend "
                 f"{jax.default_backend()!r})")
    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    for n_live in args.n_live:
        x = _inputs(n_live, rng)
        args_d = (x["q"], x["k_pages"], x["v_pages"], x["page_tables"],
                  x["seg_lens"], x["paths"], x["k_dec"], x["v_dec"],
                  x["dec_mask"])
        runs = {
            "paged": (lambda: ops.paged_bifurcated_decode_attention(
                *args_d), _walk_grid(False)),
            "packed": (lambda: ops.packed_bifurcated_decode_attention(
                *args_d, **x["chunk"]), _walk_grid(True)),
        }
        for name, (fn, grid) in runs.items():
            ms = _time(fn, args.calls)
            steps = int(np.prod(grid))
            print(json.dumps({
                "kernel": name, "n_live": n_live, "ms_per_call": ms,
                "grid": list(grid), "grid_steps": steps,
                "us_per_step": ms * 1e3 / steps,
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind}}), flush=True)


if __name__ == "__main__":
    main()
