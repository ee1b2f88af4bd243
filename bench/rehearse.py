#!/usr/bin/env python3
"""Compile each cell's step programs for a v5e that is described, not
attached, and print what ``memory_analysis()`` says they hold.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [workload ...]

For every cell (default: all in BENCHMARK.json) it builds the engine the
cell runs, at the configuration's real widths, and compiles the decode
scan at ``decode_steps`` tokens and the packed step for one chip of a
described ``v5e:2x2``. It prints, in bytes, the arguments (float32
weights, pool, decode arms, fresh-prefill envelope) and temporaries of
each program, and the pool's own size, from which a cell's ``num_pages``
is set. Nothing runs; no chip is needed.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness as H  # noqa: E402

jax, jnp = H.jax, H.jnp


def _on(sds_tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        sds_tree)


def rehearse(workload: str, sharding) -> dict:
    from repro.configs import TreeConfig
    from repro.models import get_model
    from repro.runtime.frontend import ServeFrontend
    from repro.runtime.serve import TreeServeEngine

    cell = H.load_cell(workload)
    cfg = H.model_config(cell.config)
    model = get_model(cfg)
    tcfg = TreeConfig(use_kernel=True, ctx_store="paged", step_mode="packed",
                      prefix_cache=True, suffix_prefill=True,
                      **cell.mix["engine"])
    eng = TreeServeEngine(model, cfg, tcfg)
    steps = ServeFrontend(eng).decode_steps
    params = _on(jax.eval_shape(model.init, jax.random.PRNGKey(0)), sharding)
    state = _on(jax.eval_shape(eng.init_state), sharding)
    pool = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(state.cache.store)
               if x.ndim >= 4)
    out = {"workload": workload, "pool_bytes": pool,
           "params_bytes": sum(x.size * x.dtype.itemsize
                               for x in jax.tree.leaves(params))}
    scan = eng._chunk.lower(params, state, n_steps=steps).compile()
    ma = scan.memory_analysis()
    out["scan"] = {"arguments": ma.argument_size_in_bytes,
                   "temporaries": ma.temp_size_in_bytes,
                   "outputs": ma.output_size_in_bytes,
                   "kernel": "tpu_custom_call" in scan.as_text()}
    fresh = jax.ShapeDtypeStruct(
        (cfg.n_layers, eng.pages_per_node * tcfg.page_size,
         cfg.n_kv_heads_padded, cfg.kq_dim), state.cache.k_dec.dtype,
        sharding=sharding)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
    packed = eng._packed_one.lower(
        params, state, fresh, fresh,
        jax.ShapeDtypeStruct((1, eng._prefill_chunk()), jnp.int32,
                             sharding=sharding),
        i32, i32, i32,
        jax.ShapeDtypeStruct((tcfg.depth,), jnp.int32, sharding=sharding),
    ).compile()
    ma = packed.memory_analysis()
    out["packed"] = {"arguments": ma.argument_size_in_bytes,
                     "temporaries": ma.temp_size_in_bytes,
                     "outputs": ma.output_size_in_bytes,
                     "kernel": "tpu_custom_call" in packed.as_text()}
    return out


def main():
    import json
    from unittest import mock

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    spec = H._load_json(H.ROOT, "BENCHMARK.json")
    names = sys.argv[1:] or [w["name"] for w in spec["workloads"]]
    # the kernels choose interpret mode from the default backend, which is
    # the CPU here: say "tpu" while lowering so that Mosaic compiles them
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        for name in names:
            print(json.dumps(rehearse(name, one)), flush=True)


if __name__ == "__main__":
    main()
