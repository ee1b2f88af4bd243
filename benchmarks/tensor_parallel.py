"""Paper Table 8 analog: bifurcated attention under tensor parallelism.

Claim: "the proposed context-aware bifurcated attention method works
out-of-the-box without additional modifications for tensor parallelism",
with the speedup persisting (Mistral-7B, TP=2: SDPA 246.5 ms vs bifurcated
58.0 ms at 32k/bs16 — 4.25x).

Method here: lower + compile the sharded serve_step for a reduced GQA model
on (data, model) meshes with TP in {1, 2, 4} (8 forced host devices,
subprocess), naive vs bifurcated, and compare the trip-count-aware HLO
memory bytes — the quantity the measured speedups are bound by. Asserts the
bifurcated/naive byte ratio stays large at every TP degree.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CELL = """
    import json, jax, jax.numpy as jnp
    from repro.configs import get_config, reduced_config
    from repro.launch import specs as S, steps as ST
    from repro.launch.hlo_cost import analyze

    tp = {tp}
    naive = {naive}
    # Table 8 uses Mistral-7B; the reduced stand-in keeps its GQA shape but
    # needs a long context + real batch for KV reads to dominate weights
    cfg = reduced_config(get_config("internlm2-1.8b"))
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8 // tp, tp), ("data", "model"))
    m_c, batch = 8192, 32
    with jax.sharding.set_mesh(mesh):
        model, step, rules = ST.build_serve(cfg, mesh, impl="flash")
        params = S.param_specs(model)
        io = S.decode_cache_specs(cfg, model, m_c, batch,
                                  bifurcated=not naive)
        psh = ST.to_named(mesh, ST.param_pspec_tree(params, rules, mesh=mesh))
        csh = ST.to_named(mesh, ST.cache_pspec_tree(mesh, io["cache"]))
        tsh = ST.to_named(mesh, ST.batch_pspec_tree(mesh, {{"tokens": io["tokens"]}}))["tokens"]
        ksh = ST.to_named(mesh, jax.sharding.PartitionSpec(None))
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        compiled = jax.jit(step, in_shardings=(psh, csh, tsh, ksh),
                           donate_argnums=(1,)).lower(
            params, io["cache"], io["tokens"], key).compile()
    cost = analyze(compiled.as_text())
    print(json.dumps({{"bytes": cost["bytes"], "coll": cost["collective_bytes"]}}))
"""


def _compile_cell(tp: int, naive: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # a CPU compile by design: the child must not try to take a chip the
    # parent may already hold
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent(_CELL.format(tp=tp, naive=naive))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def run(report):
    out = {}
    for tp in (1, 2, 4):
        naive = _compile_cell(tp, naive=True)
        bif = _compile_cell(tp, naive=False)
        ratio = naive["bytes"] / max(1.0, bif["bytes"])
        out[tp] = ratio
        report(f"tensor_parallel/tp{tp}_naive_bytes", naive["bytes"])
        report(f"tensor_parallel/tp{tp}_bif_bytes", bif["bytes"])
        report(f"tensor_parallel/tp{tp}_io_ratio", ratio)
    # Table 8's qualitative claim: the advantage persists at every TP degree
    for tp, ratio in out.items():
        assert ratio > 2.0, (tp, ratio)
    return out
