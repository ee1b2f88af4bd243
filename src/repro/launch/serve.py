"""Serving CLI: single-context batch sampling with bifurcated attention.

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
      --batch 16 --context 512 --steps 32 [--no-bifurcated] [--kernel]

Reduced config by default; --full runs internlm2-1.8b (or --arch) at its
published widths. The fused Pallas kernel (--kernel) compiles with Mosaic
on a TPU and runs in interpret mode on the CPU (``JAX_PLATFORMS=cpu``).
``chip_smoke.py`` at the repository root drives the full-width serving
path on one chip and checks its output.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ServeConfig, get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.runtime.serve import ServeEngine, rank_by_mean_logprob


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--context", type=int, default=256)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--no-bifurcated", action="store_true")
    ap.add_argument("--kernel", action="store_true",
                    help="use the fused Pallas decode kernel")
    ap.add_argument("--cache-dtype", default="bfloat16",
                    choices=["bfloat16", "int8"],
                    help="context-arm KV dtype; int8 streams the shared "
                         "prefix at half the bytes (core/quantized.py)")
    ap.add_argument("--top-k", type=int, default=3)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced_config(cfg)
    scfg = ServeConfig(
        batch=args.batch, context_len=args.context,
        decode_capacity=max(16, args.steps + 8),
        bifurcated=not args.no_bifurcated, use_kernel=args.kernel,
        cache_dtype=args.cache_dtype,
    )
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, cfg, scfg)

    rng = np.random.RandomState(0)
    ctx = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, args.context)))
    kwargs = {}
    if cfg.family == "vlm":
        kwargs["patch_embeds"] = jnp.asarray(
            rng.randn(1, cfg.n_image_tokens, cfg.d_model) * 0.02, jnp.float32)
    if cfg.family == "encdec":
        kwargs["frames"] = jnp.asarray(
            rng.randn(1, args.context, cfg.d_model) * 0.02, jnp.float32)
        if scfg.bifurcated:
            kwargs["sample_batch"] = args.batch

    t0 = time.perf_counter()
    result = engine.generate(params, ctx, n_steps=args.steps,
                             batch=args.batch, **kwargs)
    jax.block_until_ready(result.tokens)
    dt = time.perf_counter() - t0
    print(f"arch={cfg.name} bifurcated={engine.should_bifurcate(args.batch, args.context)} "
          f"cache_dtype={scfg.cache_dtype} "
          f"batch={args.batch} ctx={args.context} steps={args.steps}")
    print(f"wall {dt*1e3:.1f} ms  ({dt/args.steps*1e3:.2f} ms/step incl. prefill)")
    best = rank_by_mean_logprob(result, top_k=args.top_k)
    print(f"top-{args.top_k} by mean logprob: samples {best} "
          f"scores {[round(float(result.mean_logprob[i]), 3) for i in best]}")


if __name__ == "__main__":
    main()
