#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process is one run: it makes the cell's weights and traffic from the
seed, warms up (set-up), offers the traffic for ``--seconds`` of wall
clock, drains, checks what was served against the configuration's plain
reference, and prints one JSON line last on stdout:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics from a profiler trace of
the window with ``--trace 1``), ``device``, with ``--trace 1`` also
``breakdown``, and last ``check``: each number compared with its limit,
which also end stderr.

It runs only on a TPU: with no accelerator, or fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness as H  # noqa: E402


def log(msg):
    print(f"[bench] {msg}", flush=True)


def device_info(chips: int) -> dict:
    devs = H.jax.devices()[:chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    jax = H.jax
    enable_compile_cache()
    # every program, however quick to compile, is kept, so that only a
    # checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    cell = H.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < cell.chips:
        sys.exit(f"bench: {cell.name} needs {cell.chips} chips, JAX sees "
                 f"{len(devs)}")
    peaks = H.load_peaks(devs[0].device_kind)
    emit(run_cell(cell, args.seed, args.seconds, bool(args.trace), peaks,
                  PROCESS_START))


def emit(result: dict):
    """Print the checks on stderr and the result as the last stdout line,
    ``check`` last."""
    check = result.pop("check")
    for k, v in check.items():
        print(f"check {k}: {v['value']} limit {v['limit']}", file=sys.stderr,
              flush=True)
    result["check"] = check
    print(json.dumps(result), flush=True)


def run_cell(cell, seed: int, seconds: float, trace: bool, peaks: dict,
             process_start: float, cfg=None, control: bool = False) -> dict:
    """One run of ``cell``. ``cfg`` overrides the configuration's model
    (the CPU rehearsal in ``bench/tests`` passes a cut one); ``control``
    also reads the int8 control's gap on the same sample
    (``bench/calibrate.py``)."""
    jax = H.jax
    clock = H.CompileClock()
    srv = H.Server(cell, seed, cfg=cfg)
    sizes = H.ref_sizes(cell, srv.cfg)
    if cfg is not None:
        sizes.update(n_layers=cfg.n_layers, d_model=cfg.d_model,
                     n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                     head_dim=cfg.kq_dim, d_ff=cfg.d_ff,
                     vocab_size=cfg.vocab_size)
    traffic = H.Traffic(cell.mix, srv.cfg.vocab_size, seed)
    reqs = traffic.window(seconds)
    t_w = time.time()
    split = H.warm_up(srv, traffic, reqs, clock)
    log(f"cell={cell.name} seed={seed} weights+engine "
        f"{t_w - process_start:.2f}s warm-up {time.time() - t_w:.2f}s: "
        f"step shapes {split['compile_s']:.2f}s ({split['compile_n']} "
        f"compiles, {split['compile_compiling_s']:.2f}s compiling), "
        f"{split['traffic_requests']} requests {split['traffic_s']:.2f}s "
        f"({split['traffic_compiles']} compiles, "
        f"{split['traffic_compiling_s']:.2f}s compiling)")

    work, trace_dir = H.Work(), None
    if trace:
        H.instrument(srv, work)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # no Python function tracing: an event for every Python call adds
        # to the host loop that the window measures; the benchmark's own
        # spans are host annotations
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = (lambda n: jax.profiler.TraceAnnotation(n)) if trace else None
    prefix_before = dict(srv.engine.prefix_stats)
    compiles0 = clock.count
    setup_s = time.time() - process_start
    with (jax.profiler.TraceAnnotation("bench:window") if trace
          else nullcontext()):
        recs, window_s, tokens, lateness, t0, t0_ns = H.run_window(
            srv, reqs, seconds, span=span)
        jax.block_until_ready(srv.state)
    in_window = clock.count - compiles0
    prefix_after = dict(srv.engine.prefix_stats)
    window_work = H.Work(list(work.steps), list(work.step_end),
                         list(work.prefills))
    # the drain runs before the profiler stops: writing the trace out takes
    # tens of seconds, which would otherwise come out of the drain's bound
    drain_end = H.drain_records(srv, recs, t0, window_s + H.DRAIN_S)
    jax.block_until_ready(srv.state)
    if trace:
        t_s = time.time()
        jax.profiler.stop_trace()
        stop_s = time.time() - t_s
    # the peak is read before the reference runs: a process's peak never
    # falls again
    dev_info = device_info(cell.chips)
    late = sorted(lateness) or [0.0]
    log(f"window {window_s:.3f}s requests={len(recs)} "
        f"failed={sum(r.failed for r in recs)} tokens={tokens} "
        f"compiles_in_window={in_window} "
        f"generator_late_p50_ms={1e3 * late[len(late) // 2]:.3f} "
        f"generator_late_max_ms={1e3 * late[-1]:.3f} "
        f"peak_bytes_in_use={dev_info['memory_peak_bytes']}")

    run = H.Run(cell=cell, sizes=H.Sizes.from_model(sizes), peaks=peaks,
                records=recs, window_s=window_s, window_tokens=tokens,
                setup_s=setup_s, drain_end=drain_end,
                prefix_before=prefix_before, prefix_after=prefix_after,
                t0_ns=t0_ns)
    extra, breakdown = {}, None
    if trace:
        import trace_reduce as TR

        t_l = time.time()
        tr = TR.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        w = TR.host_span(tr, "bench:window")
        # the profiler keeps a bounded number of device events, so the
        # traced window ends with the last one it kept
        hi = min(w.end_ns, TR.last_op_end(tr) or w.end_ns)
        run.trace = tr
        run.trace_window = (w.start_ns, hi)
        run.clock_offset_ns = w.start_ns - t0_ns
        cut = (hi - run.clock_offset_ns) / 1e9   # on the perf_counter clock
        run.work = window_work.until(cut, sum(
            1 for r in recs if r.first is not None and t0 + r.first <= cut))
        extra = {"busy_s": TR.mean_busy_s(tr, w.start_ns, hi),
                 "window_s": (hi - w.start_ns) / 1e9}
        breakdown = TR.breakdown(tr, w.start_ns, hi)
        log(f"trace: {len(tr.ops)} device ops, traced window "
            f"{extra['window_s']:.3f}s of {w.dur_ns / 1e9:.3f}s, "
            f"{len(run.work.steps)} decode steps, "
            f"{len(run.work.prefills)} prefills; stopping the profiler "
            f"{stop_s:.1f}s, reading the trace {time.time() - t_l:.1f}s")
        for name, sec in breakdown["idle_gaps"][:5]:
            log(f"device gap {sec * 1e3:.3f} ms while host in {name}")
    metrics = H.read_metrics(cell.per_layer if trace else cell.end_to_end,
                             run)

    # the check: what the window served against the plain reference
    tickets = srv.frontend.tickets
    sample = H.check_sample(recs, tickets, int(cell.mix["check"]["requests"]),
                            seed)
    params = srv.params
    srv.free_state()
    t_c = time.time()
    got = H.check(cell, params, sample, sizes, control=control)
    lim = cell.mix["check"]
    correct = H.verdict(cell, got.mean_gap, got.tokens)
    # the widest gap and the log-prob error are printed, not compared: the
    # control reads under three times the program on them (PERF.md,
    # section 2)
    log(f"check {len(sample)} requests, {got.tokens} served tokens, "
        f"max_logit_gap={got.gap_max}, max_logprob_err={got.logprob_err}, "
        f"{time.time() - t_c:.2f}s")
    out = {"correct": correct, "attempted": len(recs),
           "failed": sum(r.failed for r in recs), "metrics": metrics,
           "device": dict(dev_info, **extra)}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {
        "mean_logit_gap": {"value": got.mean_gap, "limit": lim["mean_gap"]},
        "tokens_compared": {"value": got.tokens, "limit": H.MIN_COMPARED}}
    if control:
        # the control in the program's place, judged by the same test
        out["readings"] = {"max_logit_gap": got.gap_max,
                           "max_logprob_err": got.logprob_err}
        out["control"] = {
            "correct": H.verdict(cell, got.control_mean_gap, got.tokens),
            "mean_logit_gap": got.control_mean_gap,
            "max_logit_gap": got.control_gap_max,
            "max_logprob_err": got.control_logprob_err}
    return out


if __name__ == "__main__":
    main()
