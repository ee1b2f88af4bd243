"""The benchmark's driver: one cell, one seed, one run.

It builds the system under test as users run it (``ServeFrontend.pump``
over ``TreeServeEngine`` with the paged pool, packed steps, the prefix
cache and suffix prefill, at the frontend's defaults), makes the weights
from the seed, warms up, offers the cell's traffic for a window of
wall-clock seconds, drains, and hands a ``Run`` to the metric readers in
``bench/metrics``. Then it checks what the window served against the
configuration's plain reference (``bench/refs``).

Everything that belongs to one configuration, one traffic mix or one
metric is found by name: ``bench/configs/<config>.json``,
``bench/traffic/<mix>.json``, ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from cost import Prefill, Sizes, Step  # noqa: E402
from loadgen import Request, Traffic  # noqa: E402

DRAIN_S = 60.0          # how long requests due in the window may take after it
MIN_COMPARED = 64       # fewest served tokens a check may rest on


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    config: dict           # bench/configs/<config>.json
    mix: dict              # bench/traffic/<mix>.json
    chips: int = 1
    end_to_end: List[dict] = dataclasses.field(default_factory=list)
    per_layer: List[dict] = dataclasses.field(default_factory=list)


def _load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def make_cell(name: str, config: str, traffic: str, chips: int = 1,
              root: str = ROOT) -> Cell:
    """A cell from a configuration's and a mix's files, with the metrics
    ``BENCHMARK.json`` gives a cell of that name."""
    spec = _load_json(root, "BENCHMARK.json")
    return Cell(
        name=name,
        config=_load_json(BENCH, "configs", config + ".json"),
        mix=_load_json(BENCH, "traffic", traffic + ".json"),
        chips=chips,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def load_cell(workload: str, root: str = ROOT) -> Cell:
    spec = _load_json(root, "BENCHMARK.json")
    entry = [w for w in spec["workloads"] if w["name"] == workload]
    if not entry:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    w = entry[0]
    return make_cell(w["name"], w["config"], w["traffic"], int(w["chips"]),
                     root)


def load_peaks(kind: str) -> dict:
    table = _load_json(BENCH, "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def model_config(config: dict):
    """The program's ModelConfig for a configuration file: the registered
    architecture with the file's sizes applied, checked field by field."""
    from repro.configs import ModelConfig, get_config

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    sizes = {k: v for k, v in config["model"].items() if k in fields}
    cfg = dataclasses.replace(get_config(config["arch"]), **sizes)
    for k, v in sizes.items():
        if getattr(cfg, k) != v:
            raise AssertionError(f"config {k}: program has {getattr(cfg, k)}"
                                 f", file has {v}")
    if cfg.kq_dim != config["model"]["head_dim"]:
        raise AssertionError("head_dim differs from the program's")
    return cfg


def weight_key(seed: int):
    return jax.random.PRNGKey(
        int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) >> 1)


def make_weights(model, seed: int):
    """The model's parameters from the seed, in one jitted call on the
    device, float32 as the program serves them: matrices N(0, 1/fan_in),
    norm scales 1 + 0.1 N(0, 1), biases 0."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def body(key):
        leaves = []
        for i, (path, sd) in enumerate(flat):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            if name.endswith("['scale']"):
                leaf = 1.0 + 0.1 * jax.random.normal(k, sd.shape, jnp.float32)
            elif "['b" in name.rsplit("]", 2)[-2] and sd.ndim <= 2:
                leaf = jnp.zeros(sd.shape, jnp.float32)
            else:
                top = jax.tree_util.keystr(path[:1])
                fan_in = (sd.shape[-1] if top in ("['embed']", "['lm_head']")
                          else sd.shape[-2])
                leaf = (jax.random.normal(k, sd.shape, jnp.float32)
                        / math.sqrt(fan_in))
            leaves.append(leaf.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(body)(weight_key(seed))


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

class CompileClock:
    """Backend compiles (XLA and Mosaic) and their seconds, from JAX's
    monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

class Server:
    """Weights, engine and frontend of one run."""

    def __init__(self, cell: Cell, seed: int, cfg=None):
        from repro.configs import TreeConfig
        from repro.models import get_model
        from repro.runtime.frontend import ServeFrontend
        from repro.runtime.serve import TreeServeEngine

        self.cell = cell
        self.cfg = cfg if cfg is not None else model_config(cell.config)
        self.model = get_model(self.cfg)
        self.params = make_weights(self.model, seed)
        jax.block_until_ready(self.params)
        tcfg = TreeConfig(use_kernel=True, ctx_store="paged",
                          step_mode="packed", prefix_cache=True,
                          suffix_prefill=True, **cell.mix["engine"])
        self.engine = TreeServeEngine(self.model, self.cfg, tcfg)
        self.frontend = ServeFrontend(self.engine)
        self.state = None

    def warm_compiles(self):
        """Compile the decode step at every chunk length the frontend can
        ask for, and the slot deactivation at every count, on a throwaway
        state."""
        eng, fe = self.engine, self.frontend
        st = eng.init_state()
        for n in range(1, fe.decode_steps + 1):
            st = eng.step_chunk(self.params, st, n)
        for k in range(1, eng.ecfg.slots + 1):
            st = eng.deactivate_slots(st, list(range(k)))
        jax.block_until_ready(st)
        for s in range(eng.ecfg.slots):
            eng.outputs[s], eng.logps[s] = [], []
        del st

    def submit(self, req: Request) -> int:
        return self.frontend.submit(
            [np.asarray(s)[None] for s in req.segments],
            n_samples=req.n_samples, max_new_tokens=req.max_new_tokens)

    def pump(self):
        self.state = self.frontend.pump(self.params, self.state)

    def drain(self, max_rounds: int = 100000):
        self.state = self.frontend.drain(self.params, self.state,
                                         max_rounds=max_rounds)

    def free_state(self):
        """Drop the device state of the engine (pool, decode arms, pending
        buffers) before the reference runs."""
        self.state = None
        self.engine = self.frontend = None
        gc.collect()


# ---------------------------------------------------------------------------
# one request as the load generator saw it
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    req: Request
    due: float                   # s after window start
    sent: float = 0.0
    tid: int = -1
    admitted: Optional[float] = None
    first: Optional[float] = None
    last: Optional[float] = None
    seen: int = 0
    done: bool = False
    failed: bool = False

    @property
    def total_tokens(self) -> int:
        return self.req.n_samples * self.req.max_new_tokens


@dataclasses.dataclass
class Run:
    """Everything the metric readers see."""

    cell: Cell
    sizes: Sizes
    peaks: dict
    records: List[Record]
    window_s: float
    window_tokens: int
    setup_s: float
    drain_end: float
    prefix_before: dict
    prefix_after: dict
    work: "Work" = None               # what the traced window's steps did
    trace: object = None              # trace_reduce.Trace
    trace_window: tuple = None        # (lo_ns, hi_ns) on the trace's clock
    clock_offset_ns: int = 0          # trace ns = perf_counter ns + offset
    t0_ns: int = 0                    # window start, perf_counter ns


# ---------------------------------------------------------------------------
# tracing: host spans and the work of every engine step
# ---------------------------------------------------------------------------

class _Wrapped:
    def __init__(self, fn, before=None, after=None, span=None):
        self._fn, self._before, self._after = fn, before, after
        self._span = span

    def __call__(self, *a, **k):
        if self._before is not None:
            self._before(*a, **k)
        if self._span is None:
            out = self._fn(*a, **k)
        else:
            with jax.profiler.TraceAnnotation(self._span):
                out = self._fn(*a, **k)
        if self._after is not None:
            self._after()
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


@dataclasses.dataclass
class Work:
    """The engine's work in the traced window, as its public surface shows
    it: one ``Step`` per decode step that ``step_chunk`` was asked for,
    with the perf_counter second at which its call returned, and one
    ``Prefill`` per admission, in order."""

    steps: List[Step] = dataclasses.field(default_factory=list)
    step_end: List[float] = dataclasses.field(default_factory=list)
    prefills: List[Prefill] = dataclasses.field(default_factory=list)

    def until(self, t: float, n_finished: int) -> "Work":
        """The work done by perf_counter second ``t``, when ``n_finished``
        requests had their first token. The packed step works the oldest
        admission first, so their prefills are the first ``n_finished``
        admitted; the later ones had run in part, or not at all."""
        keep = [(st, end) for st, end in zip(self.steps, self.step_end)
                if end <= t]
        return Work([st for st, _ in keep], [end for _, end in keep],
                    self.prefills[:n_finished])


def _slot_view(eng, state):
    """Active slots with their context and decode lengths, and the trie
    nodes they read, from the engine's host mirrors."""
    active = np.asarray(state.active)
    dec = np.asarray(state.cache.dec_lens)
    nodes, ctx, dls = {}, [], []
    for s in np.nonzero(active)[0]:
        rid = eng.slot_request[int(s)]
        path = eng.requests[rid]["path"] if rid in eng.requests else []
        for nid in path:
            nodes[nid] = eng.node_len[nid]
        ctx.append(sum(eng.node_len[nid] for nid in path))
        dls.append(int(dec[s]))
    return nodes, ctx, dls


# frontend and engine calls that get a host span, where the program has
# them; the spans only name the device's idle gaps in ``breakdown``
SPANNED = {
    "engine": ("audit_state", "retire_requests", "release_retired",
               "deactivate_slots"),
    "frontend": ("_admit_pass", "_expire_finished", "_decode", "_collect",
                 "_watchdog", "_check_deadlines"),
}


def instrument(srv: Server, work: Work):
    """Record into ``work`` every decode step that the frontend asks the
    engine for and every admission's suffix prefill, from the engine's
    public calls and counters, and write host spans around the frontend's
    phases and the engine's calls (traced runs only)."""
    eng, fe = srv.engine, srv.frontend

    current = []

    def on_steps(params, state, n_steps):
        nodes, ctx, dls = _slot_view(eng, state)
        current[:] = [Step(dict(nodes), ctx, [d + j + 1 for d in dls])
                      for j in range(n_steps)]

    def after_steps():
        work.steps.extend(current)
        work.step_end.extend([time.perf_counter()] * len(current))

    stats = {}

    def before_admit(*_a, **_k):
        stats.update(eng.prefix_stats)

    def after_admit():
        after = eng.prefix_stats
        if after["admits"] == stats["admits"]:
            return
        d = {k: after[k] - stats[k]
             for k in ("reused_tokens", "new_tokens", "computed_tokens")}
        total = d["reused_tokens"] + d["new_tokens"]
        work.prefills.append(Prefill(total - d["computed_tokens"],
                                     d["computed_tokens"]))

    eng.step_chunk = _Wrapped(eng.step_chunk, on_steps, after_steps,
                              span="bench:engine.step_chunk")
    eng.admit = _Wrapped(eng.admit, before_admit, after_admit,
                         span="bench:engine.admit")
    for owner, obj in (("engine", eng), ("frontend", fe)):
        for name in SPANNED[owner]:
            if hasattr(obj, name):
                setattr(obj, name, _Wrapped(
                    getattr(obj, name),
                    span=f"bench:{owner}.{name.strip('_')}"))


# ---------------------------------------------------------------------------
# warm-up, window and drain
# ---------------------------------------------------------------------------

def warm_up(srv: Server, traffic: Traffic, window: List[Request],
            clock: "CompileClock") -> Dict[str, float]:
    """Compile the cell's step shapes, then send the mix's own warm-up
    traffic through the frontend and drain it, which leaves the prefix
    cache and the pool as a running server's. Returns the seconds and the
    compiles of each part."""
    split = {}
    t, c, cs = time.time(), clock.count, clock.seconds
    srv.warm_compiles()
    split.update(compile_s=time.time() - t, compile_n=clock.count - c,
                 compile_compiling_s=clock.seconds - cs)
    t, c, cs = time.time(), clock.count, clock.seconds
    srv.state = srv.frontend.init_state()
    warm = traffic.warmup(window)
    for req in warm:
        srv.submit(req)
        srv.pump()
    srv.drain()
    jax.block_until_ready(srv.state)
    split.update(traffic_s=time.time() - t, traffic_requests=len(warm),
                 traffic_compiles=clock.count - c,
                 traffic_compiling_s=clock.seconds - cs)
    return split


def _observe(srv: Server, recs, now: float, in_window: bool) -> int:
    """Progress of every unfinished record after a pump that ended at
    ``now``; returns the tokens that reached the host."""
    from repro.runtime.frontend import COMPLETED, REJECTED, RUNNING

    fe, eng = srv.frontend, srv.engine
    new_tokens = 0
    for r in recs:
        if r.done:
            continue
        t = fe.tickets[r.tid]
        if t.status == REJECTED:
            r.done = r.failed = True
            continue
        if t.status == RUNNING:
            n = sum(len(eng.outputs[s]) for s in t.slots)
        elif t.status == COMPLETED:
            n = sum(len(x) for x in t.tokens)
        else:
            n = 0
        if t.status in (RUNNING, COMPLETED) and r.admitted is None:
            r.admitted = now
        if n > r.seen:
            if in_window:
                new_tokens += n - r.seen
            r.seen = n
        if n > 0 and r.first is None:
            r.first = now
        if r.last is None and (n >= r.total_tokens or t.status == COMPLETED):
            r.last = now
        if t.status == COMPLETED:
            r.done = True
    return new_tokens


def run_window(srv: Server, reqs: List[Request], seconds: float, *,
               span=None):
    """Offer ``reqs`` for ``seconds`` of wall clock, each submitted at its
    due time or as soon after as the loop gets to it. Returns (records,
    window seconds, window tokens, each submission's lateness, t0 on the
    perf_counter clock in seconds, and in ns)."""
    from contextlib import nullcontext

    span = span or (lambda name: nullcontext())
    clock = time.perf_counter
    recs: List[Record] = []
    qi = 0
    tokens = 0
    lateness = []
    t0 = clock()
    t0_ns = time.perf_counter_ns()
    end = 0.0
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        with span("bench:submit"):
            while qi < len(reqs) and reqs[qi].due <= now:
                r = Record(reqs[qi], due=reqs[qi].due)
                qi += 1
                r.sent = clock() - t0
                lateness.append(r.sent - r.due)
                r.tid = srv.submit(r.req)
                recs.append(r)
        if all(r.done for r in recs):
            # nothing outstanding: wait for the next arrival
            nxt = reqs[qi].due if qi < len(reqs) else seconds
            with span("bench:idle"):
                time.sleep(max(0.0, min(nxt, seconds) - now))
            continue
        with span("bench:pump"):
            srv.pump()
        end = clock() - t0
        with span("bench:observe"):
            tokens += _observe(srv, recs, end, True)
    window_s = max(end, seconds)
    return recs, window_s, tokens, lateness, t0, t0_ns


def drain_records(srv: Server, recs: List[Record], t0: float, limit: float):
    """Pump until every record is finished or ``limit`` seconds after t0;
    what is left then has failed."""
    clock = time.perf_counter
    while not all(r.done for r in recs) and clock() - t0 < limit:
        srv.pump()
        _observe(srv, recs, clock() - t0, False)
    end = clock() - t0
    for r in recs:
        if not r.done:
            r.done = r.failed = True
    return end


# ---------------------------------------------------------------------------
# the check that decides ``correct``
# ---------------------------------------------------------------------------

def load_reference(config: dict):
    path = os.path.join(BENCH, "refs", config["reference"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_ref_" + config["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_sample(recs: List[Record], tickets, k: int, seed: int):
    """Up to ``k`` finished requests, drawn from the seed, the longest
    among them; each with its distinct served sequences and, for each, the
    log-probabilities the program recorded for every sample that served
    it."""
    done = [r for r in recs if not r.failed and
            tickets[r.tid].status == "completed"]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.req.prompt_len
                                       + r.req.max_new_tokens, -r.tid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 3])
    pick = [longest] + [rest[i] for i in sorted(
        rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False))]
    out = []
    for r in pick:
        t = tickets[r.tid]
        seqs: Dict[tuple, list] = {}
        for toks, lps in zip(t.tokens, t.logprobs):
            seqs.setdefault(tuple(int(x) for x in toks), []).append(
                np.asarray(lps))
        out.append((r, seqs))
    return out


def reference_for(cell: Cell, sizes: dict, ref_mod):
    mix = cell.mix
    pre = mix.get("prefixes")
    longest = (pre["tokens"] if pre else 0) + mix["prompt"]["max"]
    n_new = mix["new_tokens"]["max"]
    return ref_mod.Reference(sizes, longest + n_new - 1, n_new)


@dataclasses.dataclass
class Readings:
    """The check's numbers over every served token of the sample: the sum
    and the largest of the gaps by which a served token's reference logit
    lies below the reference's best, and the largest difference between
    the log-probability the program recorded for a served token and the
    reference's. ``control_*``: the same for the int8 control put in the
    program's place (the gaps of the tokens it puts first, its log-prob of
    the served tokens)."""

    gap_sum: float = 0.0
    gap_max: float = 0.0
    logprob_err: float = 0.0
    tokens: int = 0
    control_gap_sum: float = 0.0
    control_gap_max: float = 0.0
    control_logprob_err: float = 0.0

    @property
    def mean_gap(self) -> float:
        return self.gap_sum / max(self.tokens, 1)

    @property
    def control_mean_gap(self) -> float:
        return self.control_gap_sum / max(self.tokens, 1)


def verdict(cell: Cell, mean_gap: float, tokens: int) -> bool:
    """``correct``: enough served tokens compared, and their mean gap below
    the reference's best within the cell's limit. The program's readings
    and the control's go through this same test."""
    return bool(tokens >= MIN_COMPARED
                and mean_gap <= cell.mix["check"]["mean_gap"])


def check(cell: Cell, params, sample, sizes: dict, *,
          control=False) -> Readings:
    ref_mod = load_reference(cell.config)
    ref = reference_for(cell, sizes, ref_mod)
    out = Readings()
    for r, seqs in sample:
        prompt = np.concatenate(r.req.segments)
        for seq, lps in seqs.items():
            # greedy samples of a request are identical: each distinct
            # sequence counts once
            lg = ref.logits(params, prompt, seq)
            gaps = ref_mod.served_gaps(lg, seq)
            out.gap_sum += float(gaps.sum())
            out.gap_max = max(out.gap_max, float(gaps.max()))
            for lp in lps:
                out.logprob_err = max(out.logprob_err,
                                      ref_mod.logprob_error(lg, seq, lp))
            out.tokens += len(seq)
            if control:
                cl = ref.logits(params, prompt, seq, control=True)
                cg = ref_mod.control_gaps(lg, cl)
                out.control_gap_sum += float(cg.sum())
                out.control_gap_max = max(out.control_gap_max,
                                          float(cg.max()))
                out.control_logprob_err = max(
                    out.control_logprob_err, ref_mod.logprob_error(
                        lg, seq, ref_mod.logprobs(cl, seq)))
    return out


def ref_sizes(cell: Cell, cfg) -> dict:
    sizes = dict(cell.config["model"])
    sizes.setdefault("head_dim", cfg.kq_dim)
    return sizes


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def load_metric(name: str):
    """The reader ``bench/metrics/<name>.py`` as a module."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, run: Run):
    return load_metric(name).read(run)


def read_metrics(defs: List[dict], run: Run) -> Dict[str, dict]:
    out = {}
    for m in defs:
        v = read_metric(m["name"], run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
