"""End-to-end behaviour tests for the paper's system: the full
prefill -> fork -> bifurcated-decode -> rerank pipeline, and the dry-run /
sharding path on a small forced-multi-device mesh (subprocess, so the main
test process keeps its single CPU device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # CI runs the slow tier in its own step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def test_end_to_end_single_context_batch_sampling():
    from repro.configs import ServeConfig, get_config, reduced_config
    from repro.core.policy import BifurcationPolicy
    from repro.models import get_model
    from repro.runtime.serve import ServeEngine, rank_by_mean_logprob

    cfg = reduced_config(get_config("h2o-danube-1.8b"))  # SWA arch
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ctx = jnp.asarray(np.random.RandomState(5).randint(
        0, cfg.vocab_size, (1, 40)))
    outs = {}
    for bif in (True, False):
        scfg = ServeConfig(batch=5, decode_capacity=16, bifurcated=bif)
        eng = ServeEngine(model, cfg, scfg,
                          policy=BifurcationPolicy(enabled=bif,
                                                   min_io_saving_bytes=0))
        outs[bif] = eng.generate(params, ctx, n_steps=10,
                                 key=jax.random.PRNGKey(1))
    agree = float(np.mean(np.asarray(outs[True].tokens)
                          == np.asarray(outs[False].tokens)))
    assert agree >= 0.85, agree  # bf16 split-sum near-tie tolerance
    top = rank_by_mean_logprob(outs[True], top_k=3)
    assert 1 <= len(top) <= 3


def _run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=560, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_serve_step_compiles_on_8_device_mesh():
    """Small-mesh version of the dry-run: lower+compile the sharded
    serve_step for a reduced arch on a (2, 4) data x model mesh and assert
    the SPMD module contains collectives and fits."""
    out = _run_subprocess("""
        import json, jax, jax.numpy as jnp
        from repro.configs import get_config, reduced_config
        from repro.launch import specs as S, steps as ST
        from repro.launch.hlo_cost import analyze

        cfg = reduced_config(get_config("internlm2-1.8b"))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh:
            model, step, rules = ST.build_serve(cfg, mesh, impl="flash")
            params = S.param_specs(model)
            io = S.decode_cache_specs(cfg, model, 64, 8, bifurcated=True)
            psh = ST.to_named(mesh, ST.param_pspec_tree(params, rules))
            csh = ST.to_named(mesh, ST.cache_pspec_tree(mesh, io["cache"]))
            tsh = ST.to_named(mesh, ST.batch_pspec_tree(mesh, {"tokens": io["tokens"]}))["tokens"]
            ksh = ST.to_named(mesh, jax.sharding.PartitionSpec(None))
            key = jax.ShapeDtypeStruct((2,), jnp.uint32)
            compiled = jax.jit(step, in_shardings=(psh, csh, tsh, ksh),
                               donate_argnums=(1,)).lower(
                params, io["cache"], io["tokens"], key).compile()
        cost = analyze(compiled.as_text())
        mem = compiled.memory_analysis()
        print(json.dumps({
            "flops": cost["flops"],
            "coll": cost["collective_bytes"],
            "arg_bytes": int(mem.argument_size_in_bytes),
        }))
    """)
    assert out["flops"] > 0
    assert out["arg_bytes"] > 0


def test_sharded_serve_step_compiles_with_int8_cache():
    """The quantized-context cache (int8 values + f32 scale leaves, both
    sequence-sharded over "model") lowers and compiles through the same
    sharded serve_step; the cache argument footprint lands well under the
    bf16 cache's."""
    out = _run_subprocess("""
        import json, jax, jax.numpy as jnp
        from repro.configs import get_config, reduced_config
        from repro.launch import specs as S, steps as ST

        cfg = reduced_config(get_config("internlm2-1.8b"))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        sizes = {}
        with mesh:
            model, step, rules = ST.build_serve(cfg, mesh, impl="flash")
            params = S.param_specs(model)
            for quant in ("none", "int8"):
                io = S.decode_cache_specs(cfg, model, 64, 8, bifurcated=True,
                                          ctx_quant=quant)
                psh = ST.to_named(mesh, ST.param_pspec_tree(params, rules))
                csh = ST.to_named(mesh, ST.cache_pspec_tree(mesh, io["cache"]))
                tsh = ST.to_named(mesh, ST.batch_pspec_tree(
                    mesh, {"tokens": io["tokens"]}))["tokens"]
                ksh = ST.to_named(mesh, jax.sharding.PartitionSpec(None))
                key = jax.ShapeDtypeStruct((2,), jnp.uint32)
                compiled = jax.jit(step, in_shardings=(psh, csh, tsh, ksh),
                                   donate_argnums=(1,)).lower(
                    params, io["cache"], io["tokens"], key).compile()
                cache_bytes = sum(
                    l.size * l.dtype.itemsize
                    for l in jax.tree.leaves(io["cache"]))
                sizes[quant] = cache_bytes
        print(json.dumps(sizes))
    """)
    # ctx arm halves; decode arm unchanged — total strictly smaller
    assert out["int8"] < out["none"]


def test_sharded_train_step_runs_on_8_device_mesh():
    """Actually EXECUTE (not just compile) one sharded train step on 8
    forced host devices — proves shardings are not just compile-coherent."""
    out = _run_subprocess("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.configs import TrainConfig, get_config, reduced_config
        from repro.launch import steps as ST
        from repro.distributed.sharding import named_sharding_tree
        from repro.data import SyntheticLMDataset

        cfg = reduced_config(get_config("internlm2-1.8b"))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        tcfg = TrainConfig(global_batch=8, seq_len=32, remat="none",
                           warmup_steps=2, total_steps=10)
        with mesh:
            model, step, rules = ST.build_train(cfg, mesh, tcfg)
            params = model.init(jax.random.PRNGKey(0))
            from repro.optim import adamw_init
            state = {"params": params, "opt_state": adamw_init(params)}
            psh = named_sharding_tree(state, mesh, rules)
            state = jax.device_put(state, psh)
            data = SyntheticLMDataset(cfg.vocab_size, 32)
            batch = {k: jnp.asarray(v) for k, v in data.batch(0, 8).items()}
            jstep = jax.jit(step, donate_argnums=(0,))
            state, m1 = jstep(state, batch)
            batch2 = {k: jnp.asarray(v) for k, v in data.batch(1, 8).items()}
            state, m2 = jstep(state, batch2)
        print(json.dumps({"loss0": float(m1["loss"]), "loss1": float(m2["loss"])}))
    """)
    assert np.isfinite(out["loss0"]) and np.isfinite(out["loss1"])
