#!/usr/bin/env python3
"""Chip smoke: run the shared-prefix serving system once on a TPU and check
what comes out. A smoke, not a benchmark: the seconds and bytes it prints
show that a phase ran and roughly how long it took, nothing more.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the sharded serve step, four chips

The model is internlm2-1.8b exactly as ``configs/internlm2_1_8b.py``
publishes it (24 layers, d_model 2048, 16 query / 8 kv heads, head_dim 128,
vocab 92544) with float32 weights drawn from a fixed seed.

One chip, in one process:

  paper    ``ServeEngine``: one shared 4096-token context, 16 samples, 32
           decode steps through the fused Pallas decode kernel, with a
           bfloat16 and then an int8 context cache.
  serving  ``ServeFrontend`` over ``TreeServeEngine`` (paged pool, packed
           steps, prefix cache, suffix prefill): 4 requests share a
           1024-token system prompt, each adds its own 256-768-token
           document and asks for 8 samples (32 slots); bfloat16, then int8.

Each phase teacher-forces one sample's emitted tokens through a float32
forward of the same weights (``train_logits`` at matmul precision
"highest") and compares the log-probs the engine recorded with it, and
checks that the compiled decode and packed steps hold Pallas kernels
(``tpu_custom_call``), not their einsum references.

``--four-chips`` runs only ``launch.steps.build_serve``'s decode step under
``MeshRules.serving`` on a (1, 4) data x model mesh (8k context, batch 16)
and compares its logits with the same step on one device.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed phase raises, so the script exits non-zero without that line,
as it does when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import zlib

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ServeConfig, TreeConfig, get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.runtime.frontend import COMPLETED, ServeFrontend  # noqa: E402
from repro.runtime.serve import ServeEngine, TreeServeEngine  # noqa: E402

ARCH = "internlm2-1.8b"
SEED = 0
CACHE_DTYPES = ("bfloat16", "int8")

# paper path
PAPER_CONTEXT, PAPER_SAMPLES, PAPER_STEPS = 4096, 16, 32
# serving path
SYSTEM_LEN, DOC_LENS, SERVE_SAMPLES, SERVE_NEW_TOKENS = (
    1024, (256, 384, 512, 768), 8, 16)
# four-chip path
TP_CONTEXT, TP_BATCH, TP_STEPS = 8192, 16, 3

# Largest |engine log-prob - float32 reference log-prob| allowed per
# emitted token, in nats. The engine runs bf16 activations (and, for int8,
# a quantized context) against a float32 forward of the same weights. On
# the CPU, at cut widths (2 layers at d_model 64; 8 layers at d_model
# 1024), the engine stays within 0.02-0.05 nats of the reference in every
# phase, while a random context of the same length moves the reference by
# 1.0-5.2 nats: the tolerance sits ten times above the one and at least
# twice below the other. Each phase checks both.
LOGP_TOL = 0.5
# Largest relative RMS difference, rms(sharded - one device) / rms(one
# device), allowed between the four-chip step's logits and one device's.
# Both run bf16 activations whose reductions split differently. On 4
# virtual CPU devices at 24 layers (d_model 1024, 8 kv heads) it is 0.045,
# and logits of another batch row differ by 1.4.
TP_REL_RMS_TOL = 0.2


class CompileClock:
    """Seconds the backend compiler spends (XLA and Mosaic), read from
    JAX's monitoring events, so a phase can report compile time apart from
    its wall time. Tracing is left out: its events nest."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r});"
                 f" this smoke runs only on a TPU")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def random_tokens(rng, n, vocab):
    return rng.randint(0, vocab, size=(n,)).astype(np.int32)


class Reference:
    """A float32 forward of the engine's weights (``train_logits`` at
    matmul precision "highest"), which teacher-forces emitted tokens."""

    def __init__(self, cfg):
        self.model = get_model(dataclasses.replace(cfg, dtype="float32"))
        self._logprobs = jax.jit(self._logprobs_body, static_argnames="first")

    def _logprobs_body(self, params, seq, targets, *, first):
        logits, _ = self.model.train_logits(params, {"tokens": seq}, None,
                                            remat="none")
        lp = jax.nn.log_softmax(logits[0, first:].astype(jnp.float32), -1)
        return jnp.take_along_axis(lp, targets[:, None], axis=-1)[:, 0]

    def error(self, params, prompt, emitted, recorded) -> float:
        """max_i |recorded[i] - log p(emitted[i] | prompt ++ emitted[:i])|."""
        prompt, emitted = np.asarray(prompt), np.asarray(emitted)
        seq = np.concatenate([prompt, emitted[:-1]])[None].astype(np.int32)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(self._logprobs(
                params, jnp.asarray(seq), jnp.asarray(emitted, jnp.int32),
                first=len(prompt) - 1))
        recorded = np.asarray(recorded, np.float32)
        if ref.shape != recorded.shape or not np.all(np.isfinite(recorded)):
            raise AssertionError(f"log-probs malformed: {recorded}")
        return float(np.max(np.abs(ref - recorded)))

    def check(self, what, params, prompt, emitted, recorded, seed):
        """The engine's log-probs against the reference on the prompt the
        engine saw, then on a random prompt of the same length: the first
        must fall within ``LOGP_TOL`` and the second outside it, or the
        check could not tell a kernel that read the wrong context.
        Returns both errors."""
        err = self.error(params, prompt, emitted, recorded)
        wrong = random_tokens(np.random.RandomState(seed), len(prompt),
                              self.model.cfg.vocab_size)
        control = self.error(params, wrong, emitted, recorded)
        if not err <= LOGP_TOL:
            raise AssertionError(f"{what}: log-prob error {err} > {LOGP_TOL}")
        if not control > LOGP_TOL:
            raise AssertionError(
                f"{what}: a wrong context gives a log-prob error of only "
                f"{control} <= {LOGP_TOL}; the check cannot tell it apart")
        return err, control


def assert_kernel(compiled_text: str, what: str):
    if "tpu_custom_call" not in compiled_text:
        raise AssertionError(f"{what}: no Pallas kernel (tpu_custom_call) in "
                             f"the compiled step")


def paper_phase(cfg, model, ref, params, cache_dtype, clock, *,
                context=PAPER_CONTEXT, samples=PAPER_SAMPLES,
                steps=PAPER_STEPS, check_kernel=True):
    """Single shared context, many samples: the paper's workload."""
    scfg = ServeConfig(batch=samples, context_len=context,
                       decode_capacity=steps, use_kernel=True,
                       cache_dtype=cache_dtype, seed=SEED)
    engine = ServeEngine(model, cfg, scfg)
    if not engine.should_bifurcate(samples, context):
        raise AssertionError("BifurcationPolicy chose the standard cache")
    ctx = random_tokens(np.random.RandomState(SEED + 1), context,
                        cfg.vocab_size)
    ctx_j = jnp.asarray(ctx)[None]
    c0, t0 = clock.seconds, time.perf_counter()
    res = engine.generate(params, ctx_j, n_steps=steps,
                          key=jax.random.PRNGKey(SEED))
    jax.block_until_ready(res.tokens)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    toks, lps = np.asarray(res.tokens), np.asarray(res.logprobs)
    if toks.shape != (samples, steps):
        raise AssertionError(f"tokens {toks.shape} != {(samples, steps)}")
    if not ((toks >= 0) & (toks < cfg.vocab_size)).all():
        raise AssertionError("token ids out of vocabulary")

    if check_kernel:
        _, cache = engine.prefill_shared(params, ctx_j, samples)
        carry = (cache, jnp.zeros((samples, 1), jnp.int32),
                 jax.random.PRNGKey(SEED), jnp.zeros((samples,), jnp.float32))
        assert_kernel(engine._decode_scan.lower(
            params, carry, n_steps=steps - 1, temperature=scfg.temperature,
            top_p=scfg.top_p).compile().as_text(), "paper decode step")
        del cache, carry

    err, control = ref.check(f"paper/{cache_dtype}", params, ctx, toks[0],
                             lps[0], seed=SEED + 5)
    log(f"phase=paper cache={cache_dtype} samples={samples} "
        f"context={context} steps={steps} compile_s={compile_s:.1f} "
        f"wall_s={wall:.1f} peak_bytes_in_use={peak_bytes()} "
        f"logp_max_abs_err={err:.4f} tol={LOGP_TOL} "
        f"wrong_context_err={control:.4f}")


def serving_phase(cfg, model, ref, params, cache_dtype, clock, *,
                  system_len=SYSTEM_LEN, doc_lens=DOC_LENS,
                  samples=SERVE_SAMPLES, new_tokens=SERVE_NEW_TOKENS,
                  page_size=128, node_capacity=1024, check_kernel=True):
    """ServeFrontend -> TreeServeEngine -> paged pool -> packed kernel."""
    tcfg = TreeConfig(
        n_nodes=8, depth=3, slots=samples * len(doc_lens),
        node_capacity=node_capacity, decode_capacity=2 * new_tokens,
        use_kernel=True, cache_dtype=cache_dtype, ctx_store="paged",
        page_size=page_size, step_mode="packed", prefix_cache=True,
        suffix_prefill=True, seed=SEED)
    engine = TreeServeEngine(model, cfg, tcfg)
    frontend = ServeFrontend(engine, decode_steps=4)
    rng = np.random.RandomState(SEED + 2)
    system = random_tokens(rng, system_len, cfg.vocab_size)
    docs = [random_tokens(rng, n, cfg.vocab_size) for n in doc_lens]
    c0, t0 = clock.seconds, time.perf_counter()
    state = frontend.init_state()
    tids = [frontend.submit([jnp.asarray(system)[None], jnp.asarray(d)[None]],
                            n_samples=samples, max_new_tokens=new_tokens)
            for d in docs]
    state = frontend.drain(params, state)
    jax.block_until_ready(state.tokens)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0

    for tid in tids:
        t = frontend.ticket(tid)
        if t.status != COMPLETED:
            raise AssertionError(f"ticket {tid} ended {t.status} ({t.reason})")
        if len(t.tokens) != samples or any(
                len(x) != new_tokens for x in t.tokens):
            raise AssertionError(
                f"ticket {tid}: {[len(x) for x in t.tokens]} tokens, "
                f"expected {samples} x {new_tokens}")
    roots = [k for k in engine.node_index if k[0] == -1]
    reused = engine.prefix_stats["reused_tokens"]
    if len(roots) != 1 or reused != (len(docs) - 1) * system_len:
        raise AssertionError(f"shared system prompt not stored once: "
                             f"{len(roots)} roots, {reused} reused tokens")
    if engine._packed_one._cache_size() < 1:
        raise AssertionError("packed steps ran the reference, not the kernel")

    if check_kernel:
        L, g, hd = cfg.n_layers, cfg.n_kv_heads_padded, cfg.kq_dim
        fresh = jax.ShapeDtypeStruct(
            (L, engine.pages_per_node * page_size, g, hd),
            state.cache.k_dec.dtype)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        assert_kernel(engine._packed_one.lower(
            params, state, fresh, fresh,
            jax.ShapeDtypeStruct((1, engine._prefill_chunk()), jnp.int32),
            i32, i32, i32, jax.ShapeDtypeStruct((tcfg.depth,), jnp.int32),
        ).compile().as_text(), "serving packed step")

    first = frontend.ticket(tids[0])
    prompt = np.concatenate([system, docs[0]])
    err, control = ref.check(f"serving/{cache_dtype}", params, prompt,
                             first.tokens[0], first.logprobs[0],
                             seed=SEED + 6)
    m = frontend.metrics()
    log(f"phase=serving cache={cache_dtype} requests={len(tids)} "
        f"slots={tcfg.slots} rounds={m['rounds']} "
        f"preemptions={m['preemptions']} compile_s={compile_s:.1f} "
        f"wall_s={wall:.1f} peak_bytes_in_use={peak_bytes()} "
        f"logp_max_abs_err={err:.4f} tol={LOGP_TOL} "
        f"wrong_context_err={control:.4f}")


def one_chip(cfg, clock):
    model = get_model(cfg)
    ref = Reference(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    log(f"phase=init params={sum(x.size for x in jax.tree.leaves(params))} "
        f"wall_s={time.perf_counter() - t0:.1f} "
        f"peak_bytes_in_use={peak_bytes()}")
    for cache_dtype in CACHE_DTYPES:
        paper_phase(cfg, model, ref, params, cache_dtype, clock)
    for cache_dtype in CACHE_DTYPES:
        serving_phase(cfg, model, ref, params, cache_dtype, clock)


def _bytes_per_device(tree) -> dict:
    out = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = (out.get(shard.device.id, 0)
                                    + shard.data.nbytes)
    return out


def tp_decode(cfg, mesh, tokens, context):
    """``launch.steps.build_serve``'s decode step on ``mesh``: params and a
    bifurcated cache of ``context`` tokens (random KV from the seed) placed
    with the serving shardings, then one decode step per entry of
    ``tokens`` (teacher forcing, so two meshes see the same inputs).
    Returns the per-step logits over the real vocabulary (the padded tail
    holds -1e30), the params and cache bytes each device holds, and each
    device's ``bytes_in_use`` while they are live."""
    batch = tokens[0].shape[0]
    from repro.launch import specs as S
    from repro.launch import steps as ST

    with jax.sharding.set_mesh(mesh):
        model, _serve_step, rules = ST.build_serve(cfg, mesh)
        p_spec = S.param_specs(model)
        psh = ST.to_named(mesh, ST.param_pspec_tree(p_spec, rules, mesh=mesh))
        io = S.decode_cache_specs(cfg, model, context + cfg.decode_capacity,
                                  batch, bifurcated=True)
        csh = ST.to_named(mesh, ST.cache_pspec_tree(mesh, io["cache"]))
        tsh = ST.to_named(mesh, ST.batch_pspec_tree(
            mesh, {"tokens": io["tokens"]}))["tokens"]

        params = jax.jit(model.init, out_shardings=psh)(
            jax.random.PRNGKey(SEED))

        def fill(key):
            def leaf(path, spec):
                name = jax.tree_util.keystr(path)
                if "ctx" in name:
                    return jax.random.normal(
                        jax.random.fold_in(key, zlib.crc32(name.encode())),
                        spec.shape, jnp.float32).astype(spec.dtype)
                return jnp.zeros(spec.shape, spec.dtype)
            return jax.tree_util.tree_map_with_path(leaf, io["cache"])

        cache = jax.jit(fill, out_shardings=csh)(jax.random.PRNGKey(SEED + 3))
        held = _bytes_per_device((params, cache))
        in_use = [int((d.memory_stats() or {}).get("bytes_in_use", -1))
                  for d in mesh.devices.flat]

        # the serve step's body, returning logits instead of a sample
        replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        step = jax.jit(
            lambda p, c, t: model.decode_step(p, c, t, rules, impl="flash"),
            in_shardings=(psh, csh, tsh), out_shardings=(replicated, csh),
            donate_argnums=(1,))
        out = []
        for tok in tokens:
            logits, cache = step(params, cache, jnp.asarray(tok))
            out.append(np.asarray(
                logits[:, -1, :cfg.vocab_size].astype(jnp.float32)))
    return np.stack(out), held, in_use


def rel_rms(a, b) -> float:
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def four_chips(cfg, clock):
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    if len(devs) < 4:
        raise AssertionError(f"--four-chips needs 4 devices, JAX sees "
                             f"{len(devs)}")
    rng = np.random.RandomState(SEED + 4)
    tokens = [rng.randint(0, cfg.vocab_size, (TP_BATCH, 1)).astype(np.int32)
              for _ in range(TP_STEPS)]
    c0, t0 = clock.seconds, time.perf_counter()
    a, held, in_use = tp_decode(cfg, make_mesh((1, 4), ("data", "model"),
                                               devices=devs[:4]),
                                tokens, TP_CONTEXT)
    wall4, comp4 = time.perf_counter() - t0, clock.seconds - c0
    log(f"phase=tp4 mesh=(data 1, model 4) context={TP_CONTEXT} "
        f"batch={TP_BATCH} steps={TP_STEPS} compile_s={comp4:.1f} "
        f"wall_s={wall4:.1f} params+cache bytes per device="
        f"{[held.get(d.id, 0) for d in devs[:4]]} "
        f"bytes_in_use per device={in_use}")
    c0, t0 = clock.seconds, time.perf_counter()
    b, held1, in_use1 = tp_decode(cfg, make_mesh((1, 1), ("data", "model"),
                                                 devices=devs[:1]),
                                  tokens, TP_CONTEXT)
    if not (np.all(np.isfinite(a)) and np.all(np.isfinite(b))):
        raise AssertionError("non-finite logits")
    rel = rel_rms(a, b)
    # the same comparison against another batch row's logits: what a
    # sharding that mixed up rows or heads would show
    control = rel_rms(a, np.roll(b, 1, axis=1))
    err = float(np.max(np.abs(a - b)))
    agree = float(np.mean(a.argmax(-1) == b.argmax(-1)))
    log(f"phase=tp1 mesh=(data 1, model 1) compile_s="
        f"{clock.seconds - c0:.1f} wall_s={time.perf_counter() - t0:.1f} "
        f"params+cache bytes on device 0={held1.get(devs[0].id, 0)} "
        f"bytes_in_use on device 0={in_use1[0]}")
    log(f"phase=tp4-vs-tp1 logit_rel_rms_err={rel:.4f} tol={TP_REL_RMS_TOL} "
        f"other_row_rel_rms={control:.4f} logit_max_abs_err={err:.4f} "
        f"logit_max_abs={np.max(np.abs(b)):.4f} argmax_agreement={agree:.4f}")
    if not rel <= TP_REL_RMS_TOL:
        raise AssertionError(f"sharded logits differ from one device's by "
                             f"a relative RMS of {rel} > {TP_REL_RMS_TOL}")
    if not control > TP_REL_RMS_TOL:
        raise AssertionError(f"another row's logits differ by only {control}"
                             f" <= {TP_REL_RMS_TOL}; the check cannot tell")
    # sharded, not replicated and not piled on one device: no device holds
    # more than half of what the one-device run holds
    share = max(held.values()) / held1[devs[0].id]
    if share > 0.5:
        raise AssertionError(f"one device holds {share:.0%} of the "
                             f"one-device params and cache")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded serve step on a (1, 4) mesh "
                         "and compare it with one device")
    args = ap.parse_args()
    require_tpu()
    clock = CompileClock()
    cfg = get_config(ARCH)
    log(f"smoke (not a benchmark): {cfg.name} layers={cfg.n_layers} "
        f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
        f"head_dim={cfg.kq_dim} vocab={cfg.vocab_size} "
        f"device={jax.devices()[0].device_kind} x{len(jax.devices())}")
    if args.four_chips:
        four_chips(cfg, clock)
    else:
        one_chip(cfg, clock)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
