"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, lower + compile the relevant
step function on the production mesh (16x16 single-pod, or 2x16x16 multi-pod)
using ShapeDtypeStruct stand-ins (no allocation), then print/record:
  * memory_analysis()   — proves the cell fits per-device HBM,
  * cost_analysis()     — HLO FLOPs / bytes for §Roofline,
  * collective schedule — parsed from the compiled HLO text.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \
      --shape decode_32k [--multi-pod] [--impl flash|einsum|naive] \
      [--out experiments/dryrun/cell.json]

Each invocation is one process: the 512-device host-platform override below
must run before jax initializes, and ONLY here (tests/benches see 1 device).
The compile targets those host devices, so the process pins JAX to the CPU
and never takes an accelerator.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import TrainConfig  # noqa: E402
from repro.launch import specs as S  # noqa: E402
from repro.launch import steps as ST  # noqa: E402
from repro.launch.hlo import roofline_terms  # noqa: E402
from repro.launch.hlo_cost import analyze  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402


def model_flops_estimate(cfg, n_params: int, kind: str, seq_len: int,
                         batch: int) -> float:
    """MODEL_FLOPS: 6·N·D train (3 passes), 2·N·D prefill/decode (fwd only).
    For MoE, N_active = N - (1 - topk/E) * expert params (estimated)."""
    n_active = n_params
    if cfg.moe is not None:
        expert_params = cfg.n_layers * cfg.moe.n_experts * 3 * cfg.d_model * cfg.d_ff
        n_active = n_params - expert_params * (1 - cfg.moe.top_k / cfg.moe.n_experts)
    tokens = batch * (seq_len if kind in ("train", "prefill") else 1)
    per_tok = 6 * n_active if kind == "train" else 2 * n_active
    return float(per_tok) * tokens


def run_cell(arch: str, shape: str, *, multi_pod: bool, impl: str = "flash",
             bifurcated: bool = True, remat: str = "full",
             train_attn: str = "chunked", ctx_layout: str = "gmk",
             params_dtype: str = "default", ctx_quant: str = "none",
             verbose: bool = True) -> dict:
    if not S.cell_supported(arch, shape):
        return {"arch": arch, "shape": shape, "skipped": True,
                "reason": "long_500k needs sub-quadratic attention "
                          "(DESIGN.md §Arch-applicability)"}
    import dataclasses
    cfg = ST.production_config(get_config(arch))
    cfg = dataclasses.replace(cfg, train_attn=train_attn, ctx_layout=ctx_layout)
    meta = S.SHAPES[shape]
    kind, seq_len, batch = meta["kind"], meta["seq_len"], meta["global_batch"]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()

    with jax.sharding.set_mesh(mesh):
        if kind == "train":
            tcfg = TrainConfig(global_batch=batch, seq_len=seq_len, remat=remat)
            model, step, rules = ST.build_train(cfg, mesh, tcfg)
            state_specs = S.train_state_specs(model)
            if params_dtype == "bf16":
                # mixed precision: bf16 compute params, f32 AdamW moments
                state_specs["params"] = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
                    if len(s.shape) >= 2 and s.dtype == jnp.float32 else s,
                    state_specs["params"],
                )
            batch_specs = S.train_batch_specs(cfg, seq_len, batch)
            state_sh = {
                "params": ST.to_named(mesh, ST.param_pspec_tree(state_specs["params"], rules, mesh=mesh)),
                "opt_state": {
                    "m": ST.to_named(mesh, ST.param_pspec_tree(state_specs["opt_state"]["m"], rules, mesh=mesh)),
                    "v": ST.to_named(mesh, ST.param_pspec_tree(state_specs["opt_state"]["v"], rules, mesh=mesh)),
                    "step": ST.to_named(mesh, jax.sharding.PartitionSpec()),
                },
            }
            batch_sh = ST.to_named(mesh, ST.batch_pspec_tree(mesh, batch_specs))
            lowered = jax.jit(
                step, in_shardings=(state_sh, batch_sh)
            ).lower(state_specs, batch_specs)
        elif kind == "prefill":
            model, step, rules = ST.build_prefill(cfg, mesh)
            params = S.param_specs(model)
            batch_specs = S.prefill_input_specs(cfg, seq_len, batch)
            params_sh = ST.to_named(mesh, ST.param_pspec_tree(params, rules, mesh=mesh))
            batch_sh = ST.to_named(mesh, ST.batch_pspec_tree(mesh, batch_specs))
            lowered = jax.jit(
                step, in_shardings=(params_sh, batch_sh)
            ).lower(params, batch_specs)
        else:  # decode
            model, step, rules = ST.build_serve(cfg, mesh, impl=impl)
            # serving stores weight matrices in bf16 (standard practice;
            # keeps decode weight-IO at inference precision)
            params = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
                if len(s.shape) >= 2 and s.dtype == jnp.float32 else s,
                S.param_specs(model),
            )
            io = S.decode_cache_specs(cfg, model, seq_len, batch,
                                      bifurcated=bifurcated and cfg.family != "xlstm",
                                      ctx_quant=ctx_quant)
            params_sh = ST.to_named(mesh, ST.param_pspec_tree(params, rules, mesh=mesh))
            cache_sh = ST.to_named(mesh, ST.cache_pspec_tree(mesh, io["cache"]))
            tok_sh = ST.to_named(
                mesh, ST.batch_pspec_tree(mesh, {"tokens": io["tokens"]})
            )["tokens"]
            key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
            key_sh = ST.to_named(mesh, jax.sharding.PartitionSpec(None))
            lowered = jax.jit(
                step, in_shardings=(params_sh, cache_sh, tok_sh, key_sh),
                donate_argnums=(1,),
            ).lower(params, io["cache"], io["tokens"], key_spec)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = dict(compiled.cost_analysis())
    hlo = compiled.as_text()
    # Trip-count-aware analysis (XLA's cost_analysis counts scan bodies once;
    # see launch/hlo_cost.py + tests/test_hlo_cost.py). All numbers are
    # PER-DEVICE (the compiled module is the per-partition SPMD program).
    corrected = analyze(hlo)
    coll = corrected["collectives"]
    coll_bytes = corrected["collective_bytes"] * chips  # global, like flops below
    flops = float(corrected["flops"]) * chips
    hbm_bytes = float(corrected["bytes"]) * chips
    n_params = S.param_count(model)
    mflops = model_flops_estimate(cfg, n_params, kind, seq_len, batch)
    roof = roofline_terms(flops=flops, hbm_bytes=hbm_bytes,
                          collective_bytes=coll_bytes, chips=chips)
    result = {
        "arch": arch, "shape": shape, "kind": kind,
        "mesh": list(mesh.devices.shape), "chips": chips,
        "impl": impl if kind == "decode" else None,
        "bifurcated": bifurcated if kind == "decode" else None,
        "n_params": n_params,
        "hlo_flops": flops,
        "hlo_bytes": hbm_bytes,
        "collective_bytes": coll_bytes,
        "collectives": coll,
        "xla_cost_analysis": {  # raw XLA numbers (scan bodies counted once)
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "model_flops": mflops,
        "useful_flops_ratio": (mflops / flops if flops else None),
        "memory": {
            "bytes_per_device_argument": int(mem.argument_size_in_bytes),
            "bytes_per_device_output": int(mem.output_size_in_bytes),
            "bytes_per_device_temp": int(mem.temp_size_in_bytes),
            "bytes_per_device_total": int(
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes),
        },
        "roofline": roof,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    if verbose:
        print(f"== {arch} x {shape} on {result['mesh']} "
              f"({'multi-pod' if multi_pod else 'single-pod'}) ==")
        print(f"  params           {n_params/1e9:.3f} B")
        print(f"  memory/device    arg={mem.argument_size_in_bytes/1e9:.3f} GB "
              f"temp={mem.temp_size_in_bytes/1e9:.3f} GB")
        print(f"  HLO flops        {flops:.3e}   model flops {mflops:.3e} "
              f"(useful {result['useful_flops_ratio'] and round(result['useful_flops_ratio'],3)})")
        print(f"  HLO bytes        {hbm_bytes:.3e}")
        print(f"  collective bytes {coll_bytes:.3e}  {{"
              + ", ".join(f"{k}:{v['count']}" for k, v in coll.items()) + "}")
        r = roof
        print(f"  roofline         comp={r['t_compute_s']*1e3:.3f}ms "
              f"mem={r['t_memory_s']*1e3:.3f}ms coll={r['t_collective_s']*1e3:.3f}ms "
              f"-> {r['dominant']} bound")
        print(f"  lower/compile    {t_lower:.1f}s / {t_compile:.1f}s")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(S.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--impl", default="flash",
                    choices=["flash", "einsum", "naive"])
    ap.add_argument("--remat", default="full", choices=["full", "dots", "none"])
    ap.add_argument("--train-attn", default="chunked",
                    choices=["chunked", "flash"])
    ap.add_argument("--ctx-layout", default="gmk", choices=["mgk", "gmk"])
    ap.add_argument("--params-dtype", default="default",
                    choices=["default", "bf16"])
    ap.add_argument("--ctx-quant", default="none", choices=["none", "int8"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    bifurcated = args.impl != "naive"
    impl = "flash" if args.impl == "naive" else args.impl
    result = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                      impl=impl, bifurcated=bifurcated, remat=args.remat,
                      train_attn=args.train_attn, ctx_layout=args.ctx_layout,
                      params_dtype=args.params_dtype, ctx_quant=args.ctx_quant)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
