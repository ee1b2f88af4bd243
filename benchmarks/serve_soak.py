"""Serving soak harness: bursty replay against the CRASH-CONSISTENT frontend.

The paper's serving story (massively parallel decoding over shared
prefixes) is exercised here as a WORKLOAD, not a kernel: a seeded replay
of Poisson + bursty arrivals, Zipf-popular shared prefixes with MIXED
context-length distributions (short/medium/long prefixes, per-request
suffix lengths), and multi-sample pass@k requests drives
``runtime/recovery.DurableFrontend`` over a paged ``TreeServeEngine``
whose page pool is deliberately OVERSUBSCRIBED, with a seeded
``runtime/faults.FaultPlan`` drawing from the FULL registered fault set —
pool exhaustion, mid-decode cancels, delayed retirement, double-release
attempts, AND the durability faults: ``kill_process`` (the frontend dies
mid-workload and is resurrected from snapshot + journal replay),
``snapshot_corrupt`` (recovery must detect the bit-flip and fall back),
``journal_truncate`` (replay stops at the last complete record).

What must hold (the robustness acceptance bar, asserted here):
  * zero unhandled exceptions over the whole soak — kills are CAUGHT,
    recovered from, and the workload resumes across the crash boundary;
  * every surviving request ends ``completed`` with its EXACT token
    budget, ``rejected`` with a typed reason, or preempted-then-
    ``completed``;
  * ``PageAllocator.audit()`` passes at every scheduler round on BOTH
    sides of every crash (including replayed rounds).

Emits ``BENCH_serve_soak.json``: p50/p99 per-token latency, completed
tokens/sec, rejection/preemption counts, pool occupancy, durability
stats (kills survived, recoveries, replayed rounds, snapshot fallbacks)
and the PREFIX-CACHE economics — trie hit rate and shared-ancestor KV
bytes saved vs cold prefill — for the faulty soak and a fault-free
control of the same workload. ``BENCH_SOAK_FAST=1`` selects the CI
subset. Run standalone via ``PYTHONPATH=src python -m
benchmarks.serve_soak``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TreeConfig, get_config, reduced_config
from repro.models import get_model
from repro.runtime.faults import (FaultEvent, FaultKind, FaultPlan,
                                  ProcessKilled)
from repro.runtime.frontend import COMPLETED, REJECTED, ServeFrontend
from repro.runtime.recovery import DurableFrontend
from repro.runtime.serve import TreeServeEngine

BENCH_JSON = (pathlib.Path(__file__).resolve().parent.parent
              / "BENCH_serve_soak.json")

# Engine envelope: small enough to pump quickly on CPU, oversubscribed
# enough that bursts MUST queue/preempt. Worst-case paged demand is
# n_nodes * pages_needed(node_capacity + decode_capacity) = 6 * 3 pages;
# the pool holds 11 (~60%). The measured engines run with the
# CROSS-REQUEST prefix cache + suffix-only prefill ON; an evict-eagerly
# baseline of the same schedule quantifies what the cache buys (the
# bench asserts strict token-reuse improvement).
TCFG = dict(n_nodes=6, depth=2, slots=8, node_capacity=24,
            decode_capacity=12, temperature=0.0, ctx_store="paged",
            page_size=16, num_pages=11, prefix_cache=True,
            suffix_prefill=True)
TCFG_EAGER = dict(TCFG, prefix_cache=False, suffix_prefill=False)
N_PREFIXES = 4          # distinct shared system prompts (Zipf-ranked)
# mixed context-length distributions (satellite of the durability PR):
# prefixes come in short/medium/long flavours, suffix length is drawn
# per request — so page counts per node vary and the allocator sees a
# realistic mix instead of one uniform shape.
PREFIX_LENS = [8, 14, 20, 24]      # per Zipf rank (all <= node_capacity)
SUFFIX_LENS = [3, 6, 10]
SUFFIX_P = [0.4, 0.4, 0.2]


def _workload(seed: int, rounds: int, rate: float, burst_every: int,
              burst_size: int, zipf_a: float = 1.4):
    """Seeded arrival schedule: per round, Poisson(rate) arrivals plus a
    periodic burst; each request picks a shared prefix Zipf-by-rank (each
    rank has its own length), a suffix length from ``SUFFIX_LENS``, a
    pass@k sample count in {1, 2, 4}, a priority in {0, 1, 2}, and (for a
    quarter of them) a deadline."""
    rng = np.random.RandomState(seed)
    sched = []
    for r in range(rounds):
        n = int(rng.poisson(rate))
        if burst_every and r % burst_every == burst_every - 1:
            n += burst_size
        evs = []
        for _ in range(n):
            evs.append(dict(
                prefix=min(int(rng.zipf(zipf_a)) - 1, N_PREFIXES - 1),
                suffix_len=int(rng.choice(SUFFIX_LENS, p=SUFFIX_P)),
                n_samples=int(rng.choice([1, 2, 4], p=[0.5, 0.3, 0.2])),
                priority=int(rng.randint(0, 3)),
                deadline=(int(rng.randint(20, 40))
                          if rng.rand() < 0.25 else None),
            ))
        sched.append(evs)
    return sched


def _prefixes(cfg, seed: int):
    rng = np.random.RandomState(seed + 101)
    return rng, [jnp.asarray(rng.randint(0, cfg.vocab_size, (1, n)))
                 for n in PREFIX_LENS[:N_PREFIXES]]


def _check_terminal(tickets, max_new_tokens: int):
    """The acceptance bar: every surviving ticket terminal, in an allowed
    end state, with its EXACT completion budget."""
    for t in tickets:
        assert t.status in (COMPLETED, REJECTED), (t.tid, t.status)
        if t.status == REJECTED:
            assert t.reason, t.tid
        else:
            assert t.tokens is not None and all(
                len(tok) == max_new_tokens for tok in t.tokens), t.tid


def _prefix_economics(engine, state) -> dict:
    """Trie hit rates (full/partial split — a partial match is NOT a full
    hit), token-weighted reuse, and shared-ancestor KV bytes saved vs
    cold prefill (core.io_model.suffix_prefill_saving over the engine's
    token counters, at the pool's actual per-token byte cost)."""
    from repro.core.io_model import suffix_prefill_saving

    ps = dict(engine.prefix_stats)
    store = state.cache.store
    # per-token KV bytes: k + v (+ int8 scales when present), all layers
    bpt = 0
    for name in ("k_pages", "v_pages", "k_scale_pages", "v_scale_pages"):
        pool = getattr(store, name, None)
        if pool is None:
            continue
        per_tok = pool.dtype.itemsize
        for ax, dim in enumerate(pool.shape):
            if ax not in (1, 3):     # page axis, token-within-page axis
                per_tok *= dim
        bpt += per_tok
    total = ps["reused_tokens"] + ps["new_tokens"]
    hits = ps["full_hits"] + ps["partial_hits"]
    cfg = engine.cfg
    # effective bytes/element so the io_model totals match the pool's
    # actual per-token cost (2 for bf16; ~1 + scale overhead for int8)
    per_el = max(1, round(bpt / (2 * cfg.n_layers
                                 * cfg.n_kv_heads * cfg.kq_dim)))
    saving = suffix_prefill_saving(
        m_anc=ps["reused_tokens"], m_new=ps["new_tokens"],
        g=cfg.n_kv_heads, hd=cfg.kq_dim, n_layers=cfg.n_layers,
        bytes_per_el=per_el)
    ps.update(
        hit_rate=round(hits / ps["admits"], 4) if ps["admits"] else None,
        full_hit_rate=(round(ps["full_hits"] / ps["admits"], 4)
                       if ps["admits"] else None),
        token_reuse_rate=(round(ps["reused_tokens"] / total, 4)
                          if total else None),
        kv_bytes_per_token=bpt,
        prefill_bytes_saved=ps["reused_tokens"] * bpt,
        cold_prefill_bytes=total * bpt,
        io_model=saving,
    )
    return ps


def _pool_occupancy(engine, state) -> float:
    occ = engine.occupancy(state)
    return (occ["pages_total"] - occ["pages_free"]) / occ["pages_total"]


def _soak_durable(model, cfg, params, sched, *, seed: int, fault_plan,
                  workdir: str, max_new_tokens: int = 6):
    """Replay one arrival schedule through a DurableFrontend, surviving
    every ``kill_process`` by recovering from snapshot + journal and
    resuming mid-workload. Returns (dfe, prefix_econ, wall_seconds, pool
    occupancy after each pump).
    Raises on any invariant violation — the soak's job is to prove there
    are none."""
    dfe = DurableFrontend(
        lambda: TreeServeEngine(model, cfg, TreeConfig(**TCFG)),
        workdir, fault_plan=fault_plan, snapshot_every=6,
        frontend_kwargs=dict(queue_depth=32, stall_rounds=6))
    dfe.init_state()
    rng, prefixes = _prefixes(cfg, seed)
    t0 = time.perf_counter()
    total_rounds = len(sched)
    submitted_upto = 0   # schedule rounds whose arrivals are journaled
    pumps = 0
    occ = []
    while dfe.fe.round < total_rounds or dfe.pending():
        pumps += 1
        assert pumps <= total_rounds + 400, "soak liveness failure"
        target = dfe.fe.round + 1
        if target <= total_rounds and target > submitted_upto:
            # arrivals are submitted EXACTLY once: after a crash the
            # journal replay restores every submit it recorded, and
            # submits lost to journal_truncate vanish by design — the
            # suffix RNG stream is never re-consumed, so surviving
            # requests keep their original content.
            for ev in sched[target - 1]:
                suffix = jnp.asarray(
                    rng.randint(0, cfg.vocab_size, (1, ev["suffix_len"])))
                dfe.submit([prefixes[ev["prefix"]], suffix],
                           n_samples=ev["n_samples"],
                           max_new_tokens=max_new_tokens,
                           priority=ev["priority"],
                           deadline_rounds=ev["deadline"])
            submitted_upto = target
        try:
            dfe.pump(params)
            occ.append(_pool_occupancy(dfe.fe.engine, dfe.state))
        except ProcessKilled:
            # the frontend just "died" between rounds: resurrect it from
            # disk and resume — the loop re-pumps from the recovered round
            dfe.recover(params)
    wall = time.perf_counter() - t0
    _check_terminal(dfe.fe.tickets, max_new_tokens)
    econ = _prefix_economics(dfe.fe.engine, dfe.state)
    return dfe, econ, wall, occ


def _soak_plain(model, cfg, params, sched, *, seed: int,
                max_new_tokens: int = 6, tcfg=None, policy="fifo"):
    """Fault-free control: same schedule, same pump cadence, plain
    ServeFrontend (no durability layer in the measured path). ``tcfg``
    selects the engine envelope (cached default vs evict-eager
    baseline); ``policy`` selects the admission policy for the A/B
    (fifo vs sharing on the SAME seeded schedule). Returns (fe,
    prefix_econ, wall_seconds, pool occupancy after each pump)."""
    engine = TreeServeEngine(model, cfg, TreeConfig(**(tcfg or TCFG)))
    fe = ServeFrontend(engine, queue_depth=32, stall_rounds=6,
                       policy=policy)
    state = fe.init_state()
    rng, prefixes = _prefixes(cfg, seed)
    occ = []
    t0 = time.perf_counter()
    for evs in sched:
        for ev in evs:
            suffix = jnp.asarray(
                rng.randint(0, cfg.vocab_size, (1, ev["suffix_len"])))
            fe.submit([prefixes[ev["prefix"]], suffix],
                      n_samples=ev["n_samples"],
                      max_new_tokens=max_new_tokens,
                      priority=ev["priority"],
                      deadline_rounds=ev["deadline"])
        state = fe.pump(params, state)
        occ.append(_pool_occupancy(engine, state))
    while any(not t.terminal for t in fe.tickets):
        assert fe.round < len(sched) + 400, "soak liveness failure"
        state = fe.pump(params, state)
        occ.append(_pool_occupancy(engine, state))
    wall = time.perf_counter() - t0
    _check_terminal(fe.tickets, max_new_tokens)
    econ = _prefix_economics(engine, state)
    return fe, econ, wall, occ


def _summarize(fe: ServeFrontend, econ: dict, wall: float,
               occ: list) -> dict:
    m = fe.metrics()
    done = [t for t in fe.tickets if t.status == COMPLETED]
    tokens = sum(sum(len(tok) for tok in t.tokens) for t in done)
    m.update(
        wall_s=round(wall, 3),
        admission_policy=fe.policy.name,
        completed_tokens=tokens,
        tokens_per_s=round(tokens / wall, 2) if wall else None,
        preempted_then_completed=sum(
            1 for t in done if t.preemptions > 0),
        pool_occupancy=dict(mean=round(float(np.mean(occ)), 4),
                            max=round(float(np.max(occ)), 4)),
        prefix_cache=econ,
    )
    return m


def run(report) -> dict:
    fast = os.environ.get("BENCH_SOAK_FAST", "") == "1"
    rounds = 12 if fast else 40
    seed = 0
    sched = _workload(seed, rounds, rate=0.6 if fast else 0.9,
                      burst_every=5, burst_size=3 if fast else 5)
    n_requests = sum(len(e) for e in sched)
    # full registered fault-kind set — including kill_process /
    # snapshot_corrupt / journal_truncate (FaultPlan.random draws from
    # FaultKind.registered() at call time)
    plan = FaultPlan.random(seed + 7, rounds, rate=0.25, max_arg=4,
                            max_hold=3)
    if not any(e.kind == FaultKind.KILL_PROCESS for e in plan.events):
        # the crash boundary is the whole point of the durable soak:
        # guarantee at least one mid-workload kill even when the random
        # draw produced none (small fast-subset plans)
        plan.events = sorted(
            plan.events + [FaultEvent(max(2, rounds // 2),
                                      FaultKind.KILL_PROCESS)],
            key=lambda e: e.round)

    cfg = reduced_config(get_config("internlm2-1.8b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    with tempfile.TemporaryDirectory(prefix="serve_soak_") as workdir:
        dfe, econ_f, wall_fault, occ_f = _soak_durable(
            model, cfg, params, sched, seed=seed, fault_plan=plan,
            workdir=workdir)
    fe_clean, econ_c, wall_clean, occ_c = _soak_plain(model, cfg, params,
                                                      sched, seed=seed)
    # evict-eagerly baseline of the SAME schedule: its only reuse is
    # within-batch sharing between concurrently-live requests — the
    # persistent cache must strictly beat it on token-weighted reuse
    # (the cross-request revivals) and at least match its hit rate.
    fe_eager, econ_e, wall_eager, occ_e = _soak_plain(
        model, cfg, params, sched, seed=seed, tcfg=TCFG_EAGER)
    assert econ_c["token_reuse_rate"] > econ_e["token_reuse_rate"], (
        econ_c["token_reuse_rate"], econ_e["token_reuse_rate"])
    assert econ_c["hit_rate"] >= econ_e["hit_rate"], (
        econ_c["hit_rate"], econ_e["hit_rate"])
    assert econ_c["computed_tokens"] < econ_e["computed_tokens"], (
        econ_c["computed_tokens"], econ_e["computed_tokens"])

    # ADMISSION-POLICY A/B: ONE seeded Zipf schedule drained under both
    # policy="fifo" and policy="sharing" (runtime/scheduler.py). The A/B
    # uses a CONTENDED variant of the workload — deeper bursts, flatter
    # Zipf — because admission order only matters while a queue is
    # backed up (the durability schedule above drains almost every
    # round, leaving nothing to reorder). The sharing policy must
    # strictly lower the modelled context bytes/step (co-scheduled
    # sharers amortize their ancestors' reads), at least match
    # token-weighted prefix reuse, and reject NOTHING extra on deadline
    # (the slack lane's contract).
    ab_kw = dict(rate=0.9, burst_every=2, burst_size=8, zipf_a=1.1)
    ab_sched = _workload(seed, rounds, **ab_kw)
    fe_fifo, econ_pf, wall_pf, occ_pf = _soak_plain(
        model, cfg, params, ab_sched, seed=seed, policy="fifo")
    fe_shar, econ_s, wall_shar, occ_s = _soak_plain(
        model, cfg, params, ab_sched, seed=seed, policy="sharing")
    fifo_io = fe_fifo.metrics()["modelled_io"]
    shar_io = fe_shar.metrics()["modelled_io"]
    assert shar_io["ctx_bytes_per_step"] < fifo_io["ctx_bytes_per_step"], (
        shar_io, fifo_io)
    assert econ_s["token_reuse_rate"] >= econ_pf["token_reuse_rate"], (
        econ_s["token_reuse_rate"], econ_pf["token_reuse_rate"])
    dead_fifo = fe_fifo.metrics()["rejections_by_reason"].get(
        "deadline_exceeded", 0)
    dead_shar = fe_shar.metrics()["rejections_by_reason"].get(
        "deadline_exceeded", 0)
    assert dead_shar <= dead_fifo, (dead_shar, dead_fifo)

    payload = {
        "meta": {
            "device": jax.devices()[0].platform,
            "fast_subset": fast,
            "seed": seed,
            "engine": dict(TCFG),
            "workload": dict(rounds=rounds, requests=n_requests,
                             prefixes=N_PREFIXES,
                             prefix_lens=PREFIX_LENS[:N_PREFIXES],
                             suffix_lens=SUFFIX_LENS, suffix_p=SUFFIX_P),
            "fault_plan": dict(seed=plan.seed, events=len(plan),
                               kinds=plan.counts()),
            "note": ("Poisson+burst arrivals, Zipf shared prefixes with "
                     "mixed context lengths, pass@k sampling over an "
                     "oversubscribed paged trie with the cross-request "
                     "prefix cache + suffix-only prefill ON; faulty soak "
                     "(incl. process kills survived via snapshot+journal "
                     "recovery) vs fault-free control vs evict-eagerly "
                     "baseline vs sharing-policy admission A/B of the "
                     "same schedule."),
        },
        "faulty": _summarize(dfe.fe, econ_f, wall_fault, occ_f),
        "fault_free": _summarize(fe_clean, econ_c, wall_clean, occ_c),
        "fault_free_evict_eager": _summarize(fe_eager, econ_e, wall_eager,
                                             occ_e),
        # the policy axis: fifo vs sharing on ONE contended seeded Zipf
        # schedule (full per-arm summaries below; this block is the
        # asserted comparison in one place)
        "policy_ab": {
            "schedule": dict(ab_kw, rounds=rounds, seed=seed,
                             requests=sum(len(e) for e in ab_sched)),
            "fifo": {
                "ctx_bytes_per_step": fifo_io["ctx_bytes_per_step"],
                "total_bytes_per_step": fifo_io["total_bytes_per_step"],
                "token_reuse_rate": econ_pf["token_reuse_rate"],
                "deadline_rejections": dead_fifo,
            },
            "sharing": {
                "ctx_bytes_per_step": shar_io["ctx_bytes_per_step"],
                "total_bytes_per_step": shar_io["total_bytes_per_step"],
                "token_reuse_rate": econ_s["token_reuse_rate"],
                "deadline_rejections": dead_shar,
            },
            "ctx_bytes_per_step_saving": round(
                fifo_io["ctx_bytes_per_step"]
                / max(shar_io["ctx_bytes_per_step"], 1), 4),
        },
        "policy_ab_fifo": _summarize(fe_fifo, econ_pf, wall_pf, occ_pf),
        "policy_ab_sharing": _summarize(fe_shar, econ_s, wall_shar, occ_s),
    }
    payload["faulty"]["durability"] = dict(dfe.stats)
    BENCH_JSON.write_text(json.dumps(payload, indent=2))

    report("serve_soak/requests", n_requests)
    report("serve_soak/faulty_completed",
           payload["faulty"]["by_status"].get(COMPLETED, 0))
    report("serve_soak/faulty_rejected",
           payload["faulty"]["by_status"].get(REJECTED, 0))
    report("serve_soak/faulty_preemptions", payload["faulty"]["preemptions"])
    report("serve_soak/faulty_audits",
           payload["faulty"]["counters"].get("audits_passed", 0))
    report("serve_soak/faulty_tokens_per_s",
           payload["faulty"]["tokens_per_s"])
    p99 = payload["faulty"]["ttft_s"]["p99"]
    report("serve_soak/faulty_p99_ttft_ms",
           round(p99 * 1e3, 2) if p99 is not None else None)
    report("serve_soak/pool_occupancy_max",
           payload["faulty"]["pool_occupancy"]["max"])
    # every recovery in the soak loop is a survived kill_process (the
    # in-frontend fault counter dies with the killed process, faithfully)
    report("serve_soak/kills_survived", dfe.stats["recoveries"])
    report("serve_soak/replayed_rounds", dfe.stats["replayed_rounds"])
    report("serve_soak/snapshot_fallbacks", dfe.stats["snapshot_fallbacks"])
    report("serve_soak/prefix_hit_rate", econ_f["hit_rate"])
    report("serve_soak/prefix_full_hit_rate", econ_f["full_hit_rate"])
    report("serve_soak/token_reuse_rate", econ_f["token_reuse_rate"])
    report("serve_soak/token_reuse_rate_evict_eager",
           econ_e["token_reuse_rate"])
    report("serve_soak/cache_evictions", econ_f["evictions"])
    report("serve_soak/prefill_bytes_saved", econ_f["prefill_bytes_saved"])
    report("serve_soak/policy_fifo_ctx_bytes_per_step",
           fifo_io["ctx_bytes_per_step"])
    report("serve_soak/policy_sharing_ctx_bytes_per_step",
           shar_io["ctx_bytes_per_step"])
    report("serve_soak/policy_ctx_bytes_saving",
           payload["policy_ab"]["ctx_bytes_per_step_saving"])
    report("serve_soak/policy_sharing_token_reuse",
           econ_s["token_reuse_rate"])
    return payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI subset (same as BENCH_SOAK_FAST=1)")
    args = ap.parse_args()
    if args.fast:
        os.environ["BENCH_SOAK_FAST"] = "1"
    run(lambda k, v: print(f"{k},{v}"))
    print(f"wrote {BENCH_JSON}")


if __name__ == "__main__":
    main()
