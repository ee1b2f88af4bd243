"""Single-context batch-sampling serve engine (the paper's target workload).

Pipeline (paper Figure 1, bottom):
  1. ``prefill`` the ONE shared context (batch=1) -> unbatched context KV;
  2. fork ``b`` samples: BifurcatedCache broadcasts nothing — the context
     half stays head-major (L, g, m_c, hd), only the small decode half is
     per-sample;
  3. the WHOLE ``n_steps`` decode phase is ONE jitted dispatch: a
     ``lax.scan`` over decode steps with the (cache, token, key, logp)
     carry donated, tokens/logprobs stacked on-device. No per-token Python
     -> XLA round trips; with ``use_kernel`` every layer-step inside the
     scan is the single-pass fused Pallas kernel. ``loop="python"`` keeps
     the historical per-token dispatch loop as a debugging/verification
     fallback (same RNG stream, identical tokens).
  4. the BifurcationPolicy switch falls back to the fused standard cache for
     tiny workloads (paper FAQ #4), so enabling the feature is never a loss.

Also provides greedy/temperature sampling with top-p, and per-sample
mean-logprob tracking used for pass@top-k style reranking (paper §5.4).

``ForestServeEngine`` (below) is the continuous-batching generalization:
many concurrent shared-prefix requests (a prefix FOREST) served from one
slot table over grouped caches, with admit/retire as pure value updates so
the jitted decode scan compiles once for the whole serve lifetime.

``TreeServeEngine`` generalizes it once more: requests arrive as a PATH of
shared segments (system prompt -> few-shot template -> user prompt) and
admission matches the longest existing prefix path in a trie of KV node
segments — shared ancestors are stored and streamed once, not once per
request (cascade decoding, Hydragen/CoDec lineage). Same compile-once
slot-table machinery (``_SlotTableEngine``), different admission policy.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ForestConfig,
    MeshRules,
    ModelConfig,
    ServeConfig,
    TreeConfig,
)
from repro.core.errors import (
    DecodeCapacityExceeded,
    PoolExhausted,
    PrefillInFlight,
    SegmentCapacityExceeded,
    SegmentsExhausted,
    SlotsExhausted,
)
from repro.core.kv_cache import BifurcatedCache, DecodeCache
from repro.core.policy import BifurcationPolicy
from repro.runtime.telemetry import event, span, spanned


def sample_tokens(key, logits, temperature: float, top_p: float):
    """logits: (b, V) -> token ids (b,). Nucleus + temperature sampling."""
    logits = logits.astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)  # first index past p
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1)


@dataclasses.dataclass
class GenerationResult:
    tokens: jnp.ndarray        # (b, n_steps)
    mean_logprob: jnp.ndarray  # (b,) ranking score (paper §5.4 pass@top-k)
    logprobs: jnp.ndarray      # (b, n_steps)


class ServeEngine:
    def __init__(self, model, cfg: ModelConfig, scfg: ServeConfig,
                 rules: Optional[MeshRules] = None,
                 policy: Optional[BifurcationPolicy] = None):
        self.model = model
        self.cfg = cfg
        self.scfg = scfg
        self.rules = rules
        self.policy = policy or BifurcationPolicy(enabled=scfg.bifurcated)
        self._decode_jit = jax.jit(
            functools.partial(self._decode_body),
            donate_argnums=(1,),
            static_argnames=("temperature", "top_p"),
        )
        # the whole decode phase as ONE dispatch (lax.scan over steps);
        # n_steps is static — one compile per generation length.
        self._decode_scan = jax.jit(
            self._decode_scan_body,
            donate_argnums=(1,),
            static_argnames=("n_steps", "temperature", "top_p"),
        )
        # python-visible dispatch counter for the decode phase (tested).
        self.decode_dispatches = 0

    # ---- policy ----
    def should_bifurcate(self, batch: int, m_c: int) -> bool:
        return self.policy.should_bifurcate(
            batch=batch, m_c=m_c,
            n_groups=self.cfg.n_kv_heads_padded, head_dim=self.cfg.kq_dim,
        )

    # ---- engine steps ----
    def prefill_shared(self, params, context_tokens, batch: int, **kwargs):
        """context_tokens: (1, m_c). Returns (first logits, cache)."""
        cfg, model = self.cfg, self.model
        m_c = context_tokens.shape[1]
        bifurcated = self.should_bifurcate(batch, m_c)
        if cfg.family in ("dense", "moe", "vlm"):
            logits, cache1 = model.prefill(params, context_tokens, self.rules, **kwargs)
            if bifurcated and self.scfg.ctx_store == "paged":
                # paged substrate (core/paged.py): the context lands in a
                # page pool sized to exactly ceil(m_c / page_size) pages;
                # decode walks the live-page list (page-granular DMA). The
                # quant store carries the int8 + scale pages.
                from repro.core.paged import PagedBifurcatedCache

                cache = PagedBifurcatedCache.from_prefill(
                    cache1.k[:, 0], cache1.v[:, 0], batch,
                    self.scfg.decode_capacity, dtype=cache1.k.dtype,
                    page_m=self.scfg.page_size,
                    ctx_quant="int8" if self.scfg.cache_dtype == "int8"
                    else "none")
            elif bifurcated:
                # cache_dtype="int8" selects the quantized family: the int8
                # context arm is quantized ONCE at cache build (write-once
                # read-many), the decode arm stays bf16, and the jitted scan
                # decode dispatch is unchanged (registered pytree, static
                # ctx_layout, donated like the bf16 carry).
                from repro.core.quantized import ctx_cache_family

                fam = ctx_cache_family(
                    "int8" if self.scfg.cache_dtype == "int8" else "none")
                cache = fam.from_prefill(
                    cache1.k[:, 0], cache1.v[:, 0], batch,
                    self.scfg.decode_capacity, dtype=cache1.k.dtype,
                    ctx_layout=cfg.ctx_layout)
            else:
                L = cache1.k.shape[0]
                pad = self.scfg.decode_capacity
                k = jnp.pad(jnp.broadcast_to(cache1.k, (L, batch, *cache1.k.shape[2:])),
                            ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                v = jnp.pad(jnp.broadcast_to(cache1.v, (L, batch, *cache1.v.shape[2:])),
                            ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                cache = DecodeCache(k=k, v=v, length=cache1.length)
        elif cfg.family == "encdec":
            # size the decode arm from the SERVE config, like the dense path
            kwargs.setdefault("dec_capacity", self.scfg.decode_capacity)
            if bifurcated and self.scfg.cache_dtype == "int8":
                kwargs.setdefault("ctx_quant", "int8")
            logits, cache = model.prefill(
                params, context_tokens, self.rules, bifurcated=bifurcated, **kwargs)
            if not bifurcated:
                cache = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (x.shape[0], batch, *x.shape[2:]))
                    if hasattr(x, "ndim") and x.ndim >= 3 else x, cache)
        else:  # state caches: broadcast final state to the sample batch
            if cfg.family == "hybrid":
                # align the model's attn-cache family with the engine's
                # policy decision + cache dtype (the shared attention block
                # is the only quantizable arm of a hybrid), and size the
                # decode arm from the SERVE config like the dense path
                kwargs.setdefault("bifurcated", bifurcated)
                kwargs.setdefault("dec_capacity", self.scfg.decode_capacity)
                if kwargs["bifurcated"] and self.scfg.cache_dtype == "int8":
                    kwargs.setdefault("ctx_quant", "int8")
            logits, cache1 = model.prefill(params, context_tokens, self.rules, **kwargs)
            def bcast(x):
                if not hasattr(x, "ndim") or x.ndim < 2:
                    return x
                # batch axis differs per leaf family; handled by model helpers
                return x
            cache = self._broadcast_state(cache1, batch)
        logits_b = jnp.broadcast_to(logits, (batch, logits.shape[-1]))
        return logits_b, cache

    def _broadcast_state(self, cache, batch):
        cfg = self.cfg
        if cfg.family == "xlstm":
            return {
                "mlstm": jnp.broadcast_to(
                    cache["mlstm"],
                    (*cache["mlstm"].shape[:2], batch, *cache["mlstm"].shape[3:])),
                "slstm_h": jnp.broadcast_to(
                    cache["slstm_h"],
                    (cache["slstm_h"].shape[0], batch, *cache["slstm_h"].shape[2:])),
                "slstm_c": jnp.broadcast_to(
                    cache["slstm_c"],
                    (cache["slstm_c"].shape[0], batch, *cache["slstm_c"].shape[2:])),
                "position": cache["position"],
            }
        if cfg.family == "hybrid":
            from repro.core.quantized import QuantBifurcatedCache

            mam = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (x.shape[0], batch, *x.shape[2:])),
                cache["mamba"])
            attn = cache["attn"]
            if isinstance(attn, (BifurcatedCache, QuantBifurcatedCache)):
                # both bifurcated families: only the per-sample decode arm
                # broadcasts; context values (and scales) stay unbatched
                attn = dataclasses.replace(
                    attn,
                    k_dec=jnp.broadcast_to(
                        attn.k_dec, (attn.k_dec.shape[0], batch, *attn.k_dec.shape[2:])),
                    v_dec=jnp.broadcast_to(
                        attn.v_dec, (attn.v_dec.shape[0], batch, *attn.v_dec.shape[2:])))
            else:
                attn = DecodeCache(
                    k=jnp.broadcast_to(attn.k, (attn.k.shape[0], batch, *attn.k.shape[2:])),
                    v=jnp.broadcast_to(attn.v, (attn.v.shape[0], batch, *attn.v.shape[2:])),
                    length=attn.length)
            return {"attn": attn, "mamba": mam, "position": cache["position"]}
        raise ValueError(cfg.family)

    def _decode_body(self, params, carry, *, temperature, top_p):
        cache, tokens, key, logp_sum = carry
        key, sub = jax.random.split(key)
        logits, cache = self.model.decode_step(
            params, cache, tokens, self.rules,
            impl="kernel" if self.scfg.use_kernel else "einsum")
        logits = logits[:, -1]
        next_tok = sample_tokens(sub, logits, temperature, top_p)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tok_logp = jnp.take_along_axis(logp, next_tok[:, None], axis=-1)[:, 0]
        return (cache, next_tok[:, None], key, logp_sum + tok_logp), (next_tok, tok_logp)

    def _decode_scan_body(self, params, carry, *, n_steps, temperature, top_p):
        """The entire decode phase as one XLA computation: ``n_steps`` decode
        steps under ``lax.scan`` (per-step RNG stream identical to the
        python-loop path), tokens/logprobs stacked on-device."""

        def step(c, _):
            return self._decode_body(params, c, temperature=temperature,
                                     top_p=top_p)

        carry, (toks, lps) = jax.lax.scan(step, carry, None, length=n_steps)
        return carry, (toks, lps)   # ys: (n_steps, b)

    def generate(self, params, context_tokens, *, n_steps: int,
                 batch: Optional[int] = None, key=None, loop: str = "scan",
                 **prefill_kwargs) -> GenerationResult:
        """Prefill once, then decode ``n_steps`` tokens per sample.

        ``loop="scan"`` (default) runs the whole decode phase as a single
        jitted ``lax.scan`` dispatch; ``loop="python"`` is the historical
        one-dispatch-per-token loop (same RNG stream, identical tokens) kept
        for debugging and equivalence testing.
        """
        scfg = self.scfg
        batch = batch or scfg.batch
        if n_steps - 1 > scfg.decode_capacity:
            # the per-step KV write clamps at the last decode slot, so
            # generating past capacity would silently corrupt the decode
            # arm — reject loudly instead (same guard as step_chunk's).
            raise DecodeCapacityExceeded(
                f"n_steps={n_steps} needs {n_steps - 1} decode-cache slots "
                f"> decode_capacity={scfg.decode_capacity}; raise "
                f"ServeConfig.decode_capacity or generate fewer tokens")
        key = key if key is not None else jax.random.PRNGKey(scfg.seed)
        logits0, cache = self.prefill_shared(
            params, context_tokens, batch, **prefill_kwargs)
        key, sub = jax.random.split(key)
        tok = sample_tokens(sub, logits0, scfg.temperature, scfg.top_p)
        logp0 = jax.nn.log_softmax(logits0.astype(jnp.float32), axis=-1)
        lp = jnp.take_along_axis(logp0, tok[:, None], axis=-1)[:, 0]
        # the carry is donated into the decode dispatch — keep independent
        # copies of anything we also retain on the host side
        carry = (cache, tok[:, None], key, lp + 0.0)
        if loop == "scan":
            if n_steps > 1:
                _, (ts, ls) = self._decode_scan(
                    params, carry, n_steps=n_steps - 1,
                    temperature=scfg.temperature, top_p=scfg.top_p)
                self.decode_dispatches += 1
                tokens = jnp.concatenate([tok[:, None], ts.T], axis=1)
                logprobs = jnp.concatenate([lp[:, None], ls.T], axis=1)
            else:
                tokens, logprobs = tok[:, None], lp[:, None]
        elif loop == "python":
            toks, lps = [tok], [lp]
            for _ in range(n_steps - 1):
                carry, (t, l) = self._decode_jit(
                    params, carry, temperature=scfg.temperature,
                    top_p=scfg.top_p)
                self.decode_dispatches += 1
                toks.append(t)
                lps.append(l)
            tokens = jnp.stack(toks, axis=1)
            logprobs = jnp.stack(lps, axis=1)
        else:
            raise ValueError(f"unknown loop mode: {loop!r}")
        return GenerationResult(
            tokens=tokens,
            mean_logprob=jnp.mean(logprobs, axis=1),
            logprobs=logprobs,
        )


def rank_by_mean_logprob(result: GenerationResult, top_k: int = 3):
    """Deduplicate + rank samples by mean log-probability (paper §5.4).

    Ties are broken by sample index (stable argsort), so equal-score
    samples rank in submission order; duplicate token rows keep only their
    best-ranked occurrence. Zero-step results rank everything by score."""
    import numpy as np

    toks = np.asarray(result.tokens)
    scores = np.asarray(result.mean_logprob)
    seen, order = set(), []
    for i in np.argsort(-scores, kind="stable"):
        key = toks[i].tobytes()
        if key not in seen:
            seen.add(key)
            order.append(int(i))
    return order[:top_k]


# ---------------------------------------------------------------------------
# Continuous-batching forest engine (multi-prefix serving)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ForestState:
    """Device-side slot-table state carried through the jitted decode scan.

    Everything that changes at admit/retire time is a VALUE here (masks,
    counters, cache contents) — never a shape — which is what lets one
    compiled decode dispatch survive the whole serve lifetime.
    """

    cache: object            # GroupedBifurcatedCache | GroupedQuant...
    tokens: jnp.ndarray      # (b, 1) i32 — last sampled token per slot
    active: jnp.ndarray      # (b,) bool — slot is live (not retired/free)
    steps: jnp.ndarray       # (b,) i32  — decode steps emitted per slot
    key: jnp.ndarray         # PRNG key for sampling


class _SlotTableEngine:
    """Shared decode machinery for the slot-table serve engines
    (``ForestServeEngine`` over a flat prefix forest,
    ``TreeServeEngine`` over a hierarchical prefix trie).

    Subclasses own admission (how a request's context lands in the cache
    and slots get pointed at it) and retirement bookkeeping; everything
    here — the jitted scan chunk with the donated carry, in-carry EOS
    retirement, the decode-capacity guard, host-side output collection —
    depends only on the ``ecfg`` fields common to ``ForestConfig`` and
    ``TreeConfig`` (slots / temperature / top_p / use_kernel / eos_token /
    pad_token) and on the cache's ``dec_lens`` / ``decode_capacity``
    surface, which all slot-table cache families share.
    """

    def __init__(self, model, cfg: ModelConfig, ecfg,
                 rules: Optional[MeshRules] = None):
        self.model = model
        self.cfg = cfg
        self.ecfg = ecfg
        self.rules = rules
        self._chunk = jax.jit(
            self._chunk_body, donate_argnums=(1,), static_argnames=("n_steps",)
        )
        self.decode_dispatches = 0
        # host-side output mirrors (admission policy only — the decode
        # math depends exclusively on device-side state values)
        self.outputs = {s: [] for s in range(ecfg.slots)}   # slot -> tokens
        self.logps = {s: [] for s in range(ecfg.slots)}
        # integrity surface: per-segment CRCs recorded at write time
        # (admission) and slots whose decode output went non-finite —
        # the NaN/Inf sentinel in step_chunk feeds this, the frontend
        # quarantines the owning request through the cancel path.
        self.seg_checksums = {}     # segment/node id -> crc32 at write
        self.corrupt_slots = set()  # slots that emitted non-finite output

    # ---- decode ----
    def _decode_one(self, params, state: ForestState):
        """One slot-table decode step: advance every slot one token, gate
        the emission + slot-table updates on each slot's live bit."""
        ecfg = self.ecfg
        key, sub = jax.random.split(state.key)
        logits, cache = self.model.decode_step(
            params, state.cache, state.tokens, self.rules,
            impl="kernel" if ecfg.use_kernel else "einsum")
        logits = logits[:, -1]
        sampled = sample_tokens(sub, logits, ecfg.temperature, ecfg.top_p)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tok_logp = jnp.take_along_axis(logp, sampled[:, None], axis=-1)[:, 0]
        emit = state.active
        tok = jnp.where(emit, sampled, ecfg.pad_token)
        active = emit & (sampled != ecfg.eos_token) if ecfg.eos_token >= 0 \
            else emit
        new = ForestState(
            cache=cache,
            tokens=tok[:, None],
            active=active,
            steps=state.steps + emit.astype(jnp.int32),
            key=key,
        )
        return new, (tok, tok_logp, emit)

    def _chunk_body(self, params, state: ForestState, *, n_steps: int):
        def step(s, _):
            return self._decode_one(params, s)

        with jax.named_scope("scan_step"):
            return jax.lax.scan(step, state, None, length=n_steps)

    def step_chunk(self, params, state: ForestState, n_steps: int):
        """Run ``n_steps`` decode steps for the whole slot table as ONE
        jitted dispatch (donated carry). Appends each live slot's emitted
        tokens to the host-side output lists and returns the new state.

        Raises if the chunk would push any LIVE slot past its decode
        capacity: the per-slot KV write clamps at the last cache slot, so
        decoding past capacity silently corrupts that slot's decode arm —
        retire or shorten the chunk instead. (Slots admitted mid-lifetime
        sit at different depths; the guard tracks the deepest live one.)"""
        import numpy as np

        with span("engine.scan", n_steps=n_steps) as sp:
            with span("engine.inputs"):
                active = np.asarray(state.active)
                if active.any():
                    deepest = int(
                        np.asarray(state.cache.dec_lens)[active].max())
                    cap = state.cache.decode_capacity
                    if deepest + n_steps > cap:
                        raise DecodeCapacityExceeded(
                            f"chunk of {n_steps} steps would overflow "
                            f"decode_capacity={cap} (deepest live slot at "
                            f"{deepest}); retire slots or shorten the "
                            f"chunk")
            sp.set(decode_rows=int(active.sum()))
            with span("engine.dispatch"):
                state, (toks, lps, emits) = self._chunk(params, state,
                                                        n_steps=n_steps)
            self.decode_dispatches += 1
            with span("engine.collect"):
                self._collect_emitted(toks, lps, emits)
        return state

    def _collect_emitted(self, toks, lps, emits):
        """Append one decode chunk's emitted tokens ((T, b) stacks, or
        (b,) for a single step) to the host-side output lists, running the
        NaN/Inf corruption sentinel per emission."""
        import numpy as np

        toks, lps, emits = (np.asarray(toks), np.asarray(lps),
                            np.asarray(emits))
        if toks.ndim == 1:
            toks, lps, emits = toks[None], lps[None], emits[None]
        for t in range(toks.shape[0]):
            for s in range(toks.shape[1]):
                if not emits[t, s] or s in self.corrupt_slots:
                    continue
                if not np.isfinite(lps[t, s]):
                    # NaN/Inf sentinel: a non-finite logprob can only come
                    # from non-finite logits — i.e. the slot decoded from
                    # poisoned KV bytes. Stop collecting its (garbage)
                    # output from this step on and flag it; the frontend
                    # quarantines the owning request through the normal
                    # cancel/retire path (typed KVCorruption).
                    self.corrupt_slots.add(s)
                    continue
                self.outputs[s].append(int(toks[t, s]))
                self.logps[s].append(float(lps[t, s]))

    def _sample_first(self, key, logits0, n_samples):
        """Sample each fanned-out slot's first token from the shared
        prefill logits; returns (tokens (n,), logps (n,), live (n,) bool)
        with EOS-at-step-0 already folded into ``live``."""
        ecfg = self.ecfg
        logits_b = jnp.broadcast_to(logits0, (n_samples, logits0.shape[-1]))
        tok = sample_tokens(key, logits_b, ecfg.temperature, ecfg.top_p)
        logp0 = jax.nn.log_softmax(logits_b.astype(jnp.float32), axis=-1)
        lp = jnp.take_along_axis(logp0, tok[:, None], axis=-1)[:, 0]
        live = tok != ecfg.eos_token if ecfg.eos_token >= 0 else \
            jnp.ones_like(tok, bool)
        return tok, lp, live

    def result(self, slot: int) -> GenerationResult:
        """Per-slot GenerationResult view over the host-side output lists."""
        toks = jnp.asarray(self.outputs[slot])[None, :]
        lps = jnp.asarray(self.logps[slot])[None, :]
        return GenerationResult(
            tokens=toks, mean_logprob=jnp.mean(lps, axis=1), logprobs=lps)

    # ---- cancellation / observability (robustness surface) ----
    def deactivate_slots(self, state: ForestState,
                         slots) -> ForestState:
        """Flip the given slots' live bits off — the in-state equivalent
        of those slots sampling EOS. A value-only update (no recompile):
        the slots' lanes keep stepping masked, their outputs stay
        readable, and the normal retirement pass frees their group /
        request / pages once every sibling slot is inactive. This is the
        primitive behind preemption, per-request deadlines, and
        mid-decode cancellation in ``runtime/frontend.py``."""
        slots = list(slots)
        if not slots:
            return state
        ids = jnp.asarray(slots, jnp.int32)
        return dataclasses.replace(
            state, active=state.active.at[ids].set(False))

    def occupancy(self, state: ForestState) -> dict:
        """Host-side utilization snapshot (serve-loop observability): live
        slot count and — in paged mode — pool page occupancy."""
        import numpy as np

        occ = {
            "live_slots": int(np.asarray(state.active).sum()),
            "slots": int(self.ecfg.slots),
        }
        if getattr(self, "paged", False):
            occ["pages_free"] = int(self.page_alloc.free_count())
            occ["pages_total"] = int(self.num_pages)
        return occ

    # ---- integrity (KV checksums) ----
    def _live_segments(self):
        """Segment/node ids currently holding live context (subclass)."""
        raise NotImplementedError

    def verify_checksums(self, state: ForestState) -> bool:
        """Recompute every LIVE segment's context checksum and compare to
        the CRC recorded at write time. Raises ``KVCorruption`` on the
        first mismatch (bit-flipped snapshot, bad restore, host bug
        writing into the wrong page) — run on snapshot load and on demand
        via ``audit_state(verify_checksums=True)``."""
        from repro.core.integrity import verify_segment

        for idx in self._live_segments():
            expected = self.seg_checksums.get(idx)
            if expected is None:
                continue  # segment written before checksumming existed
            verify_segment(state.cache, idx, expected,
                           what=type(self).__name__ + " segment")
        return True

    # ---- durable-state serialization (checkpoint/recovery) ----
    def host_state(self) -> dict:
        """JSON-serializable snapshot of the host-side mirrors shared by
        every slot-table engine; subclasses extend with their own
        bookkeeping. Together with the device ``ForestState`` this is the
        engine's COMPLETE state: restoring both onto a fresh engine must
        continue bit-identically (tested)."""
        return {
            "decode_dispatches": int(self.decode_dispatches),
            "outputs": [list(self.outputs[s])
                        for s in range(self.ecfg.slots)],
            "logps": [list(self.logps[s]) for s in range(self.ecfg.slots)],
            "seg_checksums": [[int(k), int(v)]
                              for k, v in self.seg_checksums.items()],
            "corrupt_slots": sorted(int(s) for s in self.corrupt_slots),
        }

    def load_host_state(self, d: dict):
        self.decode_dispatches = int(d["decode_dispatches"])
        self.outputs = {s: [int(t) for t in toks]
                        for s, toks in enumerate(d["outputs"])}
        self.logps = {s: [float(x) for x in lps]
                      for s, lps in enumerate(d["logps"])}
        self.seg_checksums = {int(k): int(v) for k, v in d["seg_checksums"]}
        self.corrupt_slots = set(int(s) for s in d["corrupt_slots"])
        return self


class ForestServeEngine(_SlotTableEngine):
    """Continuous-batching serve loop over a prefix forest (beyond-paper).

    The paper's engine serves ONE shared context per batch; production
    traffic is a forest — many requests, each fanning out samples over its
    own prefix, admitted and retired at different times. This engine keeps
    a slot table of ``fcfg.slots`` decode lanes over ``fcfg.n_groups``
    shared-context segments:

      admit   — prefill a new request's context (batch=1), write it into a
                free segment (``write_context``: quantize/transpose once,
                by value), point free slots at it, sample each slot's first
                token from the prefill logits. No decode recompile.
      decode  — ``step_chunk`` runs n_steps of the whole slot table as ONE
                jitted ``lax.scan`` dispatch with the ForestState carry
                donated. Per-slot step counts and EOS retirement live
                INSIDE the carry: a slot that samples ``eos_token`` flips
                its own ``active`` bit mid-scan and emits ``pad_token``
                from then on (its lane keeps stepping — masked, isolated
                by the cross-slot decode mask — so shapes never change).
      retire  — host-side bookkeeping: segments whose slots have all gone
                inactive free up for the next admit; retired slots are
                reusable immediately (``assign_slots`` wipes their stale
                decode arm).
    """

    def __init__(self, model, cfg: ModelConfig, fcfg: ForestConfig,
                 rules: Optional[MeshRules] = None):
        super().__init__(model, cfg, fcfg, rules)
        self.fcfg = fcfg
        # host-side slot table mirrors (admission policy only — the decode
        # math depends exclusively on device-side ForestState values)
        self.group_live = [False] * fcfg.n_groups
        self.slot_group = [-1] * fcfg.slots
        self.paged = fcfg.ctx_store == "paged"
        if self.paged:
            from repro.core.paged import PageAllocator, pages_needed

            self.pages_per_seg = pages_needed(fcfg.ctx_capacity,
                                              fcfg.page_size)
            self.num_pages = (fcfg.num_pages if fcfg.num_pages is not None
                              else fcfg.n_groups * self.pages_per_seg)
            self.page_alloc = PageAllocator(self.num_pages)
            self.group_pages = {}        # group id -> pool page ids

    # ---- lifecycle ----
    def init_state(self) -> ForestState:
        cfg, fcfg = self.cfg, self.fcfg
        quant = "int8" if fcfg.cache_dtype == "int8" else "none"
        if self.paged:
            from repro.core.paged import PagedGroupedBifurcatedCache

            cache = PagedGroupedBifurcatedCache.init(
                cfg.n_layers, fcfg.n_groups, fcfg.slots, fcfg.ctx_capacity,
                fcfg.decode_capacity, cfg.n_kv_heads_padded, cfg.kq_dim,
                page_m=fcfg.page_size, num_pages=self.num_pages,
                ctx_quant=quant)
        else:
            from repro.core.quantized import forest_cache_family

            cache = forest_cache_family(quant).init(
                cfg.n_layers, fcfg.n_groups, fcfg.slots, fcfg.ctx_capacity,
                fcfg.decode_capacity, cfg.n_kv_heads_padded, cfg.kq_dim,
                ctx_layout=cfg.ctx_layout)
        b = fcfg.slots
        return ForestState(
            cache=cache,
            tokens=jnp.zeros((b, 1), jnp.int32),
            active=jnp.zeros((b,), bool),
            steps=jnp.zeros((b,), jnp.int32),
            key=jax.random.PRNGKey(fcfg.seed),
        )

    def free_groups(self):
        return [g for g, live in enumerate(self.group_live) if not live]

    def free_slots(self, state: ForestState, active=None):
        """Slots safe to (re)assign: never admitted, or belonging to a
        RETIRED group. An EOS'd slot of a still-live group is NOT free —
        its finished output must stay readable via ``result()`` until
        ``retire_groups`` frees the whole group (reassigning it would
        silently clobber the host-side output lists). ``active`` —
        optional host snapshot of ``state.active`` so one serve round
        pays the device→host sync once."""
        import numpy as np

        if active is None:
            active = np.asarray(state.active)
        return [int(s) for s in np.where(~active)[0]
                if self.slot_group[s] < 0
                or not self.group_live[self.slot_group[s]]]

    def admit(self, params, state: ForestState, context_tokens,
              n_samples: int) -> tuple:
        """Admit one request: prefill its context into a free segment, fan
        ``n_samples`` slots out over it, sample their first token from the
        prefill logits. Returns (state, slot_ids). EOS-at-step-0: a first
        token equal to ``eos_token`` retires the slot before it ever enters
        the decode loop (its emitted sequence is just the EOS)."""
        fcfg = self.fcfg
        m_new = int(context_tokens.shape[1])
        # admission REJECTION (never truncate / overflow silently): the
        # segment envelope bounds any context; paged mode additionally
        # gates on actually-allocatable pool pages.
        if m_new > fcfg.ctx_capacity:
            raise SegmentCapacityExceeded(
                f"context of {m_new} tokens exceeds the segment capacity "
                f"{fcfg.ctx_capacity}; rejected (raise "
                f"ForestConfig.ctx_capacity or split the request)")
        if self.paged:
            from repro.core.paged import pages_needed

            n_pg = pages_needed(m_new, fcfg.page_size)
            if n_pg > self.page_alloc.free_count():
                raise PoolExhausted(
                    f"context of {m_new} tokens needs {n_pg} pool pages, "
                    f"only {self.page_alloc.free_count()} of "
                    f"{self.num_pages} free — retire first")
        free_g = self.free_groups()
        free_s = self.free_slots(state)
        if not free_g:
            raise SegmentsExhausted(
                "no free context segment — retire first")
        if len(free_s) < n_samples:
            raise SlotsExhausted(
                f"need {n_samples} free slots, have {len(free_s)}")
        gidx, slots = free_g[0], free_s[:n_samples]

        if self.paged:
            # close the page-aliasing window BEFORE allocating: pages
            # released at retire may be handed to this admission, so every
            # retired group's stale table row is cleared first — no pool
            # page is ever referenced by two segments, and the kernel
            # never streams a page twice. (Runs after the rejection
            # checks: a rejected admit mutates nothing.)
            state = self.release_retired(state)

        logits0, cache1 = self.model.prefill(
            params, context_tokens, self.rules)
        if self.paged:
            page_ids = self.page_alloc.alloc(n_pg)
            self.group_pages[gidx] = page_ids
            cache = state.cache.write_context(
                cache1.k[:, 0], cache1.v[:, 0], gidx, page_ids)
        else:
            cache = state.cache.write_context(
                cache1.k[:, 0], cache1.v[:, 0], gidx)
        slot_ids = jnp.asarray(slots, jnp.int32)
        slot_mask = jnp.zeros((fcfg.slots,), bool).at[slot_ids].set(True)
        cache = cache.assign_slots(slot_mask, gidx)

        key, sub = jax.random.split(state.key)
        tok, lp, live = self._sample_first(sub, logits0, n_samples)

        state = ForestState(
            cache=cache,
            tokens=state.tokens.at[slot_ids, 0].set(tok),
            active=state.active.at[slot_ids].set(live),
            steps=state.steps.at[slot_ids].set(0),
            key=key,
        )
        self.group_live[gidx] = True
        # write-time integrity fingerprint over the segment's live ctx
        # bytes (re-verified at snapshot load / audit_state on demand)
        from repro.core.integrity import segment_checksum
        self.seg_checksums[gidx] = segment_checksum(cache, gidx)
        for i, s in enumerate(slots):
            self.slot_group[s] = gidx
            self.outputs[s] = [int(tok[i])]
            self.logps[s] = [float(lp[i])]
            self.corrupt_slots.discard(s)  # fresh request, fresh verdict
        return state, slots

    # ---- retire ----
    def retire_groups(self, state: ForestState, active=None):
        """Free every segment whose slots have all gone inactive. Returns
        the list of retired group ids; their slots become reusable by the
        next ``admit`` (which wipes the stale decode arms). In paged mode
        the retired groups' pool pages return to the allocator immediately
        and their stale page-table rows are cleared by the next ``admit``
        (before it allocates — no page is ever referenced by two
        segments); call ``release_retired`` to clear them right away and
        stop streaming the freed pages without waiting for an admission
        (a dense cache keeps streaming retired capacity — that envelope
        is exactly what paging removes).

        ``active`` optionally supplies a host snapshot of ``state.active``
        so a serve loop that already synced it this round doesn't pay a
        second device→host transfer."""
        import numpy as np

        if active is None:
            active = np.asarray(state.active)
        retired = []
        for g in range(self.fcfg.n_groups):
            if not self.group_live[g]:
                continue
            slots = [s for s in range(self.fcfg.slots)
                     if self.slot_group[s] == g]
            if not any(active[s] for s in slots):
                self.group_live[g] = False
                retired.append(g)
                self.seg_checksums.pop(g, None)
                if self.paged:
                    self.page_alloc.release(self.group_pages.pop(g, []))
        return retired

    def release_retired(self, state: ForestState) -> ForestState:
        """Paged mode: clear the page-table rows of every non-live group,
        structurally removing their pages from the decode kernels'
        live-page walk (ZERO bytes for freed segments — the paged
        counterpart of the dense kernels' masked-but-streamed capacity).
        Value-only update: no recompile. Dense mode: identity."""
        if not self.paged:
            return state
        cache = state.cache
        for g in range(self.fcfg.n_groups):
            if not self.group_live[g]:
                cache = cache.free_group(g)
        return dataclasses.replace(state, cache=cache)

    # ---- robustness surface ----
    def cancel_group(self, state: ForestState, group: int) -> ForestState:
        """Deactivate every slot of a LIVE group (preemption / deadline /
        client cancellation). The group's resources free through the
        normal ``retire_groups`` path — call it next; until then the
        slots' partial outputs stay readable via ``result``."""
        slots = [s for s in range(self.fcfg.slots)
                 if self.slot_group[s] == group]
        return self.deactivate_slots(state, slots)

    def _live_segments(self):
        return [g for g in range(self.fcfg.n_groups) if self.group_live[g]]

    def audit_state(self, state: ForestState,
                    extra_tracked: Sequence[int] = (),
                    verify_checksums: bool = False) -> bool:
        """Run ``PageAllocator.audit`` against the engine's device-side
        page tables (live groups' rows) and host-side page mirrors.
        ``extra_tracked`` lists pages a caller holds OUTSIDE the engine
        mirrors (e.g. the frontend's fault-stolen pages) so the refcount
        <-> holder reconciliation stays exact. Dense mode has no
        allocator: allocator checks are trivially True.
        ``verify_checksums=True`` additionally re-fingerprints every live
        segment's KV bytes against its write-time CRC (device round-trip
        per segment — on-demand, not every round)."""
        if verify_checksums:
            self.verify_checksums(state)
        if not self.paged:
            return True
        import numpy as np

        tables = np.asarray(state.cache.store.page_tables)
        rows = [tables[g] for g in range(self.fcfg.n_groups)
                if self.group_live[g]]
        tracked = [pid for ids in self.group_pages.values() for pid in ids]
        tracked.extend(int(i) for i in extra_tracked)
        return self.page_alloc.audit(rows=rows, tracked=tracked)

    # ---- durable-state serialization (checkpoint/recovery) ----
    def host_state(self) -> dict:
        d = super().host_state()
        d.update({
            "group_live": [bool(x) for x in self.group_live],
            "slot_group": [int(x) for x in self.slot_group],
        })
        if self.paged:
            d["group_pages"] = [[int(g), [int(p) for p in ids]]
                                for g, ids in self.group_pages.items()]
            d["allocator"] = self.page_alloc.state_dict()
        return d

    def load_host_state(self, d: dict):
        super().load_host_state(d)
        self.group_live = [bool(x) for x in d["group_live"]]
        self.slot_group = [int(x) for x in d["slot_group"]]
        if self.paged:
            self.group_pages = {int(g): [int(p) for p in ids]
                                for g, ids in d["group_pages"]}
            self.page_alloc.load_state_dict(d["allocator"])
        return self


# ---------------------------------------------------------------------------
# Hierarchical prefix-trie engine (cascade serving)
# ---------------------------------------------------------------------------

class TreeServeEngine(_SlotTableEngine):
    """Continuous-batching serve loop over a hierarchical prefix TRIE.

    The forest engine stores each request's full prefix in its own segment;
    real traffic shares prefix STRUCTURE — many requests open with the same
    system prompt, many of those with the same few-shot template. This
    engine keeps the trie itself: requests arrive as a path of ``segments``
    (outermost shared level first) and admission matches the LONGEST
    existing prefix path before allocating anything:

      admit   — walk the host-side trie index level by level; every level
                that matches a live node (same ancestor path, same tokens)
                is REUSED — its KV is neither recomputed into the cache nor
                re-stored. The request's full concatenation is prefilled
                once (batch=1) for the first-token logits, and only the
                NEW levels' KV slices are written into free node segments
                (``write_node``: quantize/transpose once, by value). Free
                slots are pointed at the path (``assign_paths``). All of
                this is runtime DATA — no decode recompile, ever.
      decode  — inherited ``step_chunk``: the whole slot table advances as
                ONE jitted ``lax.scan`` dispatch; every trie node's K/V
                streams from HBM once per step no matter how many paths
                traverse it (the cascade kernel's point). In-carry EOS
                retirement exactly as in the forest engine.
      retire  — ``retire_requests`` frees finished requests; node
                refcounts drop along their paths and a node's segment (and
                trie-index entry) frees only when NO live request
                references it — shared ancestors survive their children.

    With every request a single segment (depth-1 paths) this engine serves
    the exact flat-forest workload, token-identically (tested).
    """

    def __init__(self, model, cfg: ModelConfig, tcfg: TreeConfig,
                 rules: Optional[MeshRules] = None):
        super().__init__(model, cfg, tcfg, rules)
        self.tcfg = tcfg
        # host-side trie mirrors (admission policy only — decode math
        # depends exclusively on device-side state values)
        self.node_live = [False] * tcfg.n_nodes
        self.node_refs = [0] * tcfg.n_nodes          # live-request refcount
        self.node_index = {}    # (parent id, token tuple) -> node id
        self.node_key = [None] * tcfg.n_nodes        # reverse map
        self.node_len = [0] * tcfg.n_nodes           # live tokens per node
        # (host mirror of node_lens/seg_lens: eviction tie-breaks and the
        # suffix-prefill gather read it without a device sync)
        self.slot_request = [-1] * tcfg.slots
        # request table: rid -> {"path", "slots", "live"}. Holds LIVE
        # requests plus retired ones still referenced by a slot (their
        # outputs stay readable until the slot is reused); anything else
        # compacts away (_compact_requests), so the table stays O(slots)
        # on a long-running server, not O(requests-ever). rids are
        # monotonic (next_rid) and never reused — frontend tickets and
        # journal replay key on them across compaction.
        self.requests = {}
        self.next_rid = 0
        self.last_rid = -1      # rid of the most recent admit
        # cross-request prefix cache (tcfg.prefix_cache): refcount-zero
        # nodes stay RESIDENT — node_live True, pages held, trie-index
        # entry kept, checksum kept — stamped here for LRU eviction
        # under node/page pressure. Revival (a later admit matching the
        # node) just pops the stamp and bumps the refcount.
        self.node_cached = {}   # node id -> LRU stamp
        self.lru_clock = 0
        # prefix-cache accounting: every admission records how many of
        # its path tokens were REUSED from resident trie nodes (their KV
        # neither re-stored nor re-streamed at write) vs written fresh,
        # split into FULL-path and partial hits, plus how many tokens
        # actually ran through prefill (suffix-only prefill computes just
        # the new levels) — the soak harness turns this into token-
        # weighted reuse / bytes-saved.
        self.prefix_stats = {"admits": 0, "full_hits": 0,
                             "partial_hits": 0, "reused_tokens": 0,
                             "new_tokens": 0, "computed_tokens": 0,
                             "evictions": 0}
        self.paged = tcfg.ctx_store == "paged"
        if self.paged:
            from repro.core.paged import PageAllocator, pages_needed

            self.pages_per_node = pages_needed(tcfg.node_capacity,
                                               tcfg.page_size)
            self.num_pages = (tcfg.num_pages if tcfg.num_pages is not None
                              else tcfg.n_nodes * self.pages_per_node)
            self.page_alloc = PageAllocator(self.num_pages)
            self.node_pages = {}         # node id -> pool page ids
            # page sharing for trie ancestors is REFCOUNTED through the
            # node refcounts: a reused ancestor's pages are allocated once
            # at its first admission and freed only when the node's own
            # refcount hits zero (retire_requests).
        # packed heterogeneous stepping (tcfg.step_mode == "packed"):
        # admissions with NEW trie levels register a PENDING prefill here;
        # their suffix KV lands in chunks piggybacked onto decode steps
        # (one packed work-queue kernel launch per layer serves the decode
        # batch and the chunk together) and the request activates when its
        # last chunk lands. ``node_pending`` holds trie-node ids reserved
        # by in-flight prefills: not live (no KV yet, excluded from the
        # kernels' live-page walk by their zeroed seg_lens rows), not free
        # (their identity and pages are claimed).
        self._pending = {}           # rid -> pending-prefill record
        self.node_pending = set()    # node ids reserved, KV not yet written
        self._packed_one = jax.jit(self._packed_one_body,
                                   donate_argnums=(1, 2, 3))

    # ---- lifecycle ----
    def init_state(self) -> ForestState:
        """Device-side state: the same ``ForestState`` carry as the forest
        engine (tokens / active / steps / key), holding a
        ``PrefixTreeCache`` / its int8 twin — or, under
        ``ctx_store="paged"``, a ``PagedPrefixTreeCache`` over the shared
        page pool."""
        cfg, tcfg = self.cfg, self.tcfg
        quant = "int8" if tcfg.cache_dtype == "int8" else "none"
        if self.paged:
            from repro.core.paged import PagedPrefixTreeCache

            cache = PagedPrefixTreeCache.init(
                cfg.n_layers, tcfg.n_nodes, tcfg.depth, tcfg.slots,
                tcfg.node_capacity, tcfg.decode_capacity,
                cfg.n_kv_heads_padded, cfg.kq_dim,
                page_m=tcfg.page_size, num_pages=self.num_pages,
                ctx_quant=quant)
        else:
            from repro.core.quantized import tree_cache_family

            cache = tree_cache_family(quant).init(
                cfg.n_layers, tcfg.n_nodes, tcfg.depth, tcfg.slots,
                tcfg.node_capacity, tcfg.decode_capacity,
                cfg.n_kv_heads_padded, cfg.kq_dim,
                ctx_layout=cfg.ctx_layout)
        b = tcfg.slots
        return ForestState(
            cache=cache,
            tokens=jnp.zeros((b, 1), jnp.int32),
            active=jnp.zeros((b,), bool),
            steps=jnp.zeros((b,), jnp.int32),
            key=jax.random.PRNGKey(tcfg.seed),
        )

    def free_nodes(self):
        return [i for i, live in enumerate(self.node_live)
                if not live and i not in self.node_pending]

    def free_slots(self, state: ForestState, active=None):
        """Slots safe to (re)assign: never admitted, or belonging to a
        RETIRED request (same invariant as the forest engine: an EOS'd
        slot of a still-live request keeps its output readable). A slot
        whose request has been COMPACTED away counts as retired.
        ``active`` — optional host snapshot of ``state.active`` so one
        serve round pays the device→host sync once and threads it
        through free_slots / retire_requests."""
        import numpy as np

        if active is None:
            active = np.asarray(state.active)
        return [int(s) for s in np.where(~active)[0]
                if self.slot_request[s] < 0
                or not self.request_live(self.slot_request[s])]

    def request_live(self, rid: int) -> bool:
        """Is request ``rid`` still live? A compacted (long-retired) rid
        is simply not-live — slot reuse and the frontend's collection
        pass treat it exactly like a freshly-retired one."""
        req = self.requests.get(rid)
        return bool(req is not None and req["live"])

    def match_prefix(self, segments):
        """Longest-matching prefix path for ``segments`` (list of (1, m)
        token arrays, outermost level first): returns (node ids of the
        matched levels, number matched). Node identity is (ancestor node,
        token content), so a match guarantees identical KV."""
        import numpy as np

        path, parent = [], -1
        for seg in segments:
            key = (parent, tuple(int(t) for t in np.asarray(seg)[0]))
            nid = self.node_index.get(key)
            if nid is None or not self.node_live[nid]:
                break
            path.append(nid)
            parent = nid
        return path, len(path)

    def peek_prefix(self, segments):
        """Side-effect-free admission PROBE: what would ``admit`` match,
        without admitting. Returns ``(path, matched, matched_tokens)`` —
        the longest-matching prefix path's node ids, the number of
        matched levels, and the total resident tokens on that path
        (live OR cached — a cached match revives for free).

        This is the surface the admission policy
        (``runtime/scheduler.SharingPolicy``) scores candidates through:
        unlike ``admit`` it never touches refcounts, the LRU stamps, or
        ``prefix_stats`` — probing a queued request N times leaves the
        trie bit-identical, which is what keeps policy scoring
        deterministic and replay-safe."""
        path, matched = self.match_prefix(segments)
        return path, matched, sum(self.node_len[nid] for nid in path)

    def step_io_bytes(self, state: ForestState, active=None) -> dict:
        """Modelled per-DECODE-STEP HBM bytes of the current live slot
        table (per layer), via ``core.io_model.tree_decode_io_bytes``
        over the live slots' trie paths: every referenced node's context
        read once per step, plus per-slot decode arms and q/out rows.
        The frontend accumulates this per decode chunk into its
        ``io_ledger`` — the bytes/step axis the admission-policy A/B
        (benchmarks/serve_soak.py) compares policies on.

        ``active`` optionally supplies a host snapshot of
        ``state.active`` (same convention as ``free_slots``). Returns
        ``{"ctx_bytes", "total", "slots"}`` — zeros when nothing is
        decoding."""
        import numpy as np

        from repro.core.io_model import tree_decode_io_bytes

        if active is None:
            active = np.asarray(state.active)
        paths = []
        for s in range(self.tcfg.slots):
            rid = self.slot_request[s]
            if active[s] and rid >= 0 and self.request_live(rid):
                paths.append(tuple(self.requests[rid]["path"]))
        if not paths:
            return {"ctx_bytes": 0, "total": 0, "slots": 0}
        io = tree_decode_io_bytes(
            paths=paths, node_lens=self.node_len,
            c_d=self.tcfg.decode_capacity,
            g=self.cfg.n_kv_heads, hd=self.cfg.kq_dim)
        return {"ctx_bytes": int(sum(io["per_node"].values())),
                "total": int(io["total"]), "slots": len(paths)}

    # ---- cross-request prefix cache (tcfg.prefix_cache) ----
    def cached_nodes(self):
        """Refcount-zero trie nodes currently held RESIDENT as cache
        entries (sorted node ids)."""
        return sorted(self.node_cached)

    def _eviction_order(self, protect=()):
        """Cached nodes in eviction order. A candidate must have NO
        resident children (evicting a parent first would dangle its
        descendants' (parent, tokens) trie keys across node-slot reuse);
        among candidates the oldest LRU stamp goes first, ties broken
        toward the smallest subtree (fewest live tokens, then lowest
        id). Because a live descendant pins every ancestor's refcount, a
        cached node's resident descendants are all cached too — so the
        childless-first peeling below reaches everything outside
        ``protect`` (which is prefix-closed: a protected node's cached
        ancestors are on the same matched path).

        Under ``tcfg.evict_policy == "sharing"`` the primary key is the
        candidate's ancestor-shared bytes (``_ancestor_shared_bytes``):
        cold PRIVATE tails — nothing above them shared — evict before
        leaves hanging under hot shared ancestors, regardless of recency;
        the LRU stamp only breaks ties."""
        protect = set(protect)
        remaining = {n for n in self.node_cached if n not in protect}
        sharing = self.tcfg.evict_policy == "sharing"
        order = []
        while remaining:
            blocked = {self.node_key[n][0] for n in remaining}
            if sharing:
                key = lambda n: (self._ancestor_shared_bytes(n),
                                 self.node_cached[n], self.node_len[n], n)
            else:
                key = lambda n: (self.node_cached[n],
                                 self.node_len[n], n)
            nid = min((n for n in remaining if n not in blocked), key=key)
            order.append(nid)
            remaining.discard(nid)
        return order

    def _ancestor_shared_bytes(self, nid: int) -> int:
        """Per-layer context bytes of ``nid``'s resident ancestors that
        are SHARED — pinned by a live request (refcount > 0) or parenting
        >= 2 resident children — the eviction-side twin of the admission
        policy's shared-bytes score (``io_model.tree_admit_bytes_delta``).
        A node under a purely private chain scores 0."""
        children = {}
        for n in range(self.tcfg.n_nodes):
            if self.node_live[n] and self.node_key[n] is not None:
                parent = self.node_key[n][0]
                if parent >= 0:
                    children[parent] = children.get(parent, 0) + 1
        per_tok = 2 * self.cfg.n_kv_heads * self.cfg.kq_dim * 2
        total, n = 0, nid
        while True:
            key = self.node_key[n]
            if key is None or key[0] < 0:
                return total
            parent = key[0]
            if self.node_refs[parent] > 0 or children.get(parent, 0) >= 2:
                total += self.node_len[parent] * per_tok
            n = parent

    @spanned("engine.evict")
    def _evict_cached(self, state: ForestState, *, need_nodes: int = 0,
                      need_pages: int = 0, protect=()) -> ForestState:
        """Lazily evict cached nodes until ``need_nodes`` free node slots
        and ``need_pages`` allocatable pool pages exist. If the demand is
        unsatisfiable even by evicting EVERY candidate, nothing is
        evicted — the caller's typed capacity error fires and the cache
        keeps its contents. Eviction goes through the same free path as
        eager retirement (index entry, checksum and length dropped), with
        the page-table row cleared BEFORE the pages return to the
        allocator so no aliasing window opens against the allocation that
        triggered the eviction."""
        order = self._eviction_order(protect)
        victims = []
        if self.paged and need_pages:
            plan = self.page_alloc.plan_eviction(
                need_pages,
                [(n, len(self.node_pages.get(n, ()))) for n in order])
            if plan is None:
                return state
            victims = list(plan)
        short = need_nodes - len(self.free_nodes()) - len(victims)
        if short > 0:
            if len(victims) + short > len(order):
                return state
            victims = order[:len(victims) + short]
        if not victims:
            return state
        cache = state.cache
        for nid in victims:
            self.node_live[nid] = False
            self.node_cached.pop(nid, None)
            self.node_index.pop(self.node_key[nid], None)
            self.node_key[nid] = None
            self.node_len[nid] = 0
            self.seg_checksums.pop(nid, None)
            if self.paged:
                cache = cache.free_node(nid)
                self.page_alloc.release(self.node_pages.pop(nid, []))
        self.prefix_stats["evictions"] += len(victims)
        return dataclasses.replace(state, cache=cache)

    # ---- suffix-only prefill (tcfg.suffix_prefill) ----
    def _node_kv(self, cache, nid: int, m: int):
        """One resident node's first ``m`` live tokens of K/V as
        (L, m, g, hd) model-dtype tensors, read straight from the serve
        cache — dense slab slices or pool-page gathers; int8 nodes
        dequantize (k-scales carry the logit scale hd**-0.5 pre-folded,
        so K unfolds it by hd**0.5)."""
        hd = self.cfg.kq_dim
        store = getattr(cache, "store", None)
        if store is not None:
            ids = jnp.asarray(self.node_pages[nid], jnp.int32)
            k = jnp.take(store.k_pages, ids, axis=1)  # (L, npg, g, pm, hd)
            v = jnp.take(store.v_pages, ids, axis=1)
            k = k.transpose(0, 1, 3, 2, 4).reshape(
                k.shape[0], -1, k.shape[2], k.shape[4])[:, :m]
            v = v.transpose(0, 1, 3, 2, 4).reshape(
                v.shape[0], -1, v.shape[2], v.shape[4])[:, :m]
            if getattr(store, "k_scale_pages", None) is not None:
                sk = jnp.take(store.k_scale_pages, ids, axis=1)
                sv = jnp.take(store.v_scale_pages, ids, axis=1)
                sk = sk.transpose(0, 1, 3, 2).reshape(
                    sk.shape[0], -1, sk.shape[2])[:, :m]
                sv = sv.transpose(0, 1, 3, 2).reshape(
                    sv.shape[0], -1, sv.shape[2])[:, :m]
                k = k.astype(jnp.float32) * sk[..., None] * hd**0.5
                v = v.astype(jnp.float32) * sv[..., None]
        else:
            layout = getattr(cache, "ctx_layout", "gmk")
            if layout == "gmk":
                k = cache.k_ctx[:, nid, :, :m].transpose(0, 2, 1, 3)
                v = cache.v_ctx[:, nid, :, :m].transpose(0, 2, 1, 3)
            else:
                k = cache.k_ctx[:, nid, :m]
                v = cache.v_ctx[:, nid, :m]
            if getattr(cache, "k_scale", None) is not None:
                if layout == "gmk":
                    sk = cache.k_scale[:, nid, :, :m].transpose(0, 2, 1)
                    sv = cache.v_scale[:, nid, :, :m].transpose(0, 2, 1)
                else:
                    sk = cache.k_scale[:, nid, :m]
                    sv = cache.v_scale[:, nid, :m]
                k = k.astype(jnp.float32) * sk[..., None] * hd**0.5
                v = v.astype(jnp.float32) * sv[..., None]
        dtype = cache.k_dec.dtype
        return k.astype(dtype), v.astype(dtype)

    def _gather_path_kv(self, state: ForestState, path, cut: int):
        """Per-layer K/V of the matched path's first ``cut`` tokens in
        prefill layout (L, 1, cut, g, hd) — the cached context arm fed to
        ``model.prefill_suffix`` so admission never recomputes them."""
        ks, vs = [], []
        got = 0
        for nid in path:
            if got >= cut:
                break
            m = min(self.node_len[nid], cut - got)
            k, v = self._node_kv(state.cache, nid, m)
            ks.append(k)
            vs.append(v)
            got += m
        k = jnp.concatenate(ks, axis=1)
        v = jnp.concatenate(vs, axis=1)
        return k[:, None], v[:, None]

    def admit(self, params, state: ForestState, segments,
              n_samples: int) -> tuple:
        """Admit one request given as a PATH of ``segments`` — a list of
        (1, m_i) token arrays, outermost shared level first (e.g. [system
        prompt, few-shot template, user prompt]); 1 <= len <= ``depth``.

        The longest matching prefix of the path is reused from the trie;
        the full concatenation is prefilled ONCE (for exact positions /
        attention history and the first-token logits) and only the new
        levels' KV slices are written. ``n_samples`` free slots fan out
        over the path. Returns (state, slot_ids). EOS-at-step-0 retires a
        slot before it ever enters the decode loop, as in the forest
        engine."""
        with span("engine.admit") as sp:
            tcfg = self.tcfg
            segments = [jnp.asarray(s) for s in segments]
            if not 1 <= len(segments) <= tcfg.depth:
                raise ValueError(
                    f"request path of {len(segments)} levels; engine "
                    f"depth is {tcfg.depth}")
            cap = state.cache.node_capacity
            for seg in segments:
                if seg.shape[1] > cap:
                    # admission REJECTION (never truncate): the node
                    # envelope bounds any segment, dense or paged.
                    raise SegmentCapacityExceeded(
                        f"segment of {seg.shape[1]} tokens > node "
                        f"capacity {cap}")
            with span("engine.match"):
                path, matched = self.match_prefix(segments)
                new_segs = segments[matched:]
                if new_segs:
                    # collision with an IN-FLIGHT packed prefill: the first
                    # new level's (parent, tokens) identity may already be
                    # reserved by a pending admission — it can be neither
                    # reused (KV not written) nor duplicated. Retryable:
                    # clears when the pending prefill's chunks land.
                    # (Deeper new levels hang off nodes created by THIS
                    # admission, so only the first can collide.)
                    key0 = (path[-1] if path else -1,
                            tuple(int(t) for t in
                                  jax.device_get(new_segs[0])[0]))
                    nid0 = self.node_index.get(key0)
                    if nid0 is not None and nid0 in self.node_pending:
                        raise PrefillInFlight(
                            f"trie level is being prefilled by a pending "
                            f"packed admission (node {nid0}) — retry "
                            f"after its chunks land")
            sp.set(reused_tokens=sum(int(s.shape[1])
                                     for s in segments[:matched]),
                   new_tokens=sum(int(s.shape[1]) for s in new_segs))
            if tcfg.step_mode == "packed" and new_segs:
                with span("engine.reserve"):
                    state, slots = self._admit_packed(
                        params, state, segments, n_samples, path, matched)
            else:
                state, slots = self._admit_now(params, state, segments,
                                               n_samples, path, matched)
            sp.set(rid=self.last_rid)
        return state, slots

    def _admit_now(self, params, state: ForestState, segments,
                   n_samples: int, path, matched) -> tuple:
        """Synchronous admission: the prefill runs now and the slots
        activate with their first tokens sampled."""
        tcfg = self.tcfg
        new_segs = segments[matched:]
        with span("engine.reserve"):
            if tcfg.prefix_cache and len(new_segs) > len(self.free_nodes()):
                # node-slot pressure: lazily evict cached nodes (LRU,
                # children-first). The matched path is protected — it is
                # about to be revived by this very admission.
                state = self._evict_cached(state, need_nodes=len(new_segs),
                                           protect=path)
            free_n = self.free_nodes()
            free_s = self.free_slots(state)
            if len(new_segs) > len(free_n):
                raise SegmentsExhausted(
                    f"need {len(new_segs)} free trie nodes, have "
                    f"{len(free_n)} — retire first")
            if len(free_s) < n_samples:
                raise SlotsExhausted(
                    f"need {n_samples} free slots, have {len(free_s)}")
            if self.paged:
                # paged admission gates on allocatable POOL PAGES, before
                # any prefill work: reused ancestors cost zero new pages.
                from repro.core.paged import pages_needed

                n_pg = sum(pages_needed(int(s.shape[1]),
                                        self.tcfg.page_size)
                           for s in new_segs)
                if (tcfg.prefix_cache
                        and n_pg > self.page_alloc.free_count()):
                    # page pressure: same lazy eviction, the victim prefix
                    # planned by the allocator against its live free list.
                    state = self._evict_cached(state, need_pages=n_pg,
                                               protect=path)
                if n_pg > self.page_alloc.free_count():
                    raise PoolExhausted(
                        f"request needs {n_pg} pool pages for "
                        f"{len(new_segs)} new node(s), only "
                        f"{self.page_alloc.free_count()} of "
                        f"{self.num_pages} free — retire first")
                # close the page-aliasing window BEFORE allocating: freed
                # nodes' pages may be handed to this admission, so their
                # stale table rows are cleared first — no pool page is
                # ever referenced by two nodes. (After the rejection
                # checks: a rejected admit mutates nothing.)
                state = self.release_retired(state)
            slots = free_s[:n_samples]

        total = sum(int(s.shape[1]) for s in segments)
        offset = sum(int(s.shape[1]) for s in segments[:matched])
        cut = 0
        with span("engine.prefill"), \
                jax.named_scope("prefill"):
            if tcfg.suffix_prefill and matched:
                # SUFFIX-ONLY prefill: the matched ancestors' cached KV
                # is the context arm; only the new levels' tokens run
                # through the model — admission costs O(new tokens), not
                # O(path). On a FULL-path match the last cached token
                # re-runs as a 1-token suffix so the first-token logits
                # stay defined (cut < total always; nothing is rewritten).
                cut = min(offset, total - 1)
                k_anc, v_anc = self._gather_path_kv(state, path, cut)
                suffix = jnp.concatenate(segments, axis=1)[:, cut:]
                logits0, cache1 = self.model.prefill_suffix(
                    params, suffix, k_anc, v_anc, self.rules, start=cut)
            else:
                # ONE prefill of the full concatenation: reused ancestors
                # are recomputed (identical values — same tokens, same
                # positions) but NOT rewritten; each new node gets its
                # token-slice.
                full = jnp.concatenate(segments, axis=1)
                logits0, cache1 = self.model.prefill(params, full,
                                                     self.rules)
        cache = state.cache
        self.prefix_stats["admits"] += 1
        if matched == len(segments):
            self.prefix_stats["full_hits"] += 1
        elif matched:
            self.prefix_stats["partial_hits"] += 1
        self.prefix_stats["reused_tokens"] += offset
        self.prefix_stats["new_tokens"] += total - offset
        self.prefix_stats["computed_tokens"] += total - cut
        parent = path[-1] if path else -1
        for seg in new_segs:
            nid = free_n.pop(0)
            m = int(seg.shape[1])
            with span("engine.node_write"):
                if self.paged:
                    from repro.core.paged import pages_needed

                    ids = self.page_alloc.alloc(
                        pages_needed(m, self.tcfg.page_size))
                    self.node_pages[nid] = ids
                    cache = cache.write_node(
                        cache1.k[:, 0, offset - cut:offset - cut + m],
                        cache1.v[:, 0, offset - cut:offset - cut + m], nid,
                        ids)
                else:
                    cache = cache.write_node(
                        cache1.k[:, 0, offset - cut:offset - cut + m],
                        cache1.v[:, 0, offset - cut:offset - cut + m], nid)
                key = (parent, tuple(int(t) for t in
                                     jax.device_get(seg)[0]))
                self.node_index[key] = nid
                self.node_key[nid] = key
                self.node_live[nid] = True
                self.node_len[nid] = m
                # write-time integrity fingerprint (re-verified at
                # snapshot load / audit_state on demand)
                from repro.core.integrity import segment_checksum
                self.seg_checksums[nid] = segment_checksum(cache, nid)
            path.append(nid)
            parent = nid
            offset += m
        for nid in path:
            self.node_refs[nid] += 1
            self.node_cached.pop(nid, None)  # revival: cached -> live

        with span("engine.activate"):
            path_col = jnp.asarray(
                path + [-1] * (tcfg.depth - len(path)), jnp.int32)
            slot_ids = jnp.asarray(slots, jnp.int32)
            slot_mask = jnp.zeros((tcfg.slots,), bool).at[slot_ids].set(
                True)
            cache = cache.assign_paths(slot_mask, path_col)

            key, sub = jax.random.split(state.key)
            tok, lp, live = self._sample_first(sub, logits0, n_samples)

            state = ForestState(
                cache=cache,
                tokens=state.tokens.at[slot_ids, 0].set(tok),
                active=state.active.at[slot_ids].set(live),
                steps=state.steps.at[slot_ids].set(0),
                key=key,
            )
            rid = self.next_rid
            self.next_rid += 1
            self.last_rid = rid
            self.requests[rid] = {"path": list(path), "slots": list(slots),
                                  "live": True}
            for i, s in enumerate(slots):
                self.slot_request[s] = rid
                self.outputs[s] = [int(tok[i])]
                self.logps[s] = [float(lp[i])]
                self.corrupt_slots.discard(s)  # fresh request, fresh verdict
        event("request.first_token", rid=rid)
        # slot reuse may have dropped the last reference to a retired
        # request's table entry — compact it away now
        self._compact_requests()
        return state, slots

    # ---- packed heterogeneous stepping (tcfg.step_mode == "packed") ----
    def _prefill_chunk(self) -> int:
        return self.tcfg.prefill_chunk or self.tcfg.page_size

    def _admit_packed(self, params, state: ForestState, segments,
                      n_samples: int, path, matched) -> tuple:
        """Packed-mode admission of a request with NEW trie levels: all
        validation, eviction, page allocation and trie registration happen
        NOW (host bookkeeping + allocator, no device writes, no prefill),
        and the suffix prefill is deferred to chunks piggybacked onto
        subsequent decode steps (``_packed_step``). The reserved slots
        activate — ``assign_paths`` + first-token sampling — only when the
        LAST chunk lands. Until then the request is live (its slots are
        not reusable) but decodes nothing."""
        import numpy as np

        tcfg = self.tcfg
        new_segs = segments[matched:]
        if tcfg.prefix_cache and len(new_segs) > len(self.free_nodes()):
            state = self._evict_cached(state, need_nodes=len(new_segs),
                                       protect=path)
        free_n = self.free_nodes()
        free_s = self.free_slots(state)
        if len(new_segs) > len(free_n):
            raise SegmentsExhausted(
                f"need {len(new_segs)} free trie nodes, have {len(free_n)}"
                " — retire first")
        if len(free_s) < n_samples:
            raise SlotsExhausted(
                f"need {n_samples} free slots, have {len(free_s)}")
        if self.paged:
            from repro.core.paged import pages_needed

            n_pg = sum(pages_needed(int(s.shape[1]), tcfg.page_size)
                       for s in new_segs)
            if tcfg.prefix_cache and n_pg > self.page_alloc.free_count():
                state = self._evict_cached(state, need_pages=n_pg,
                                           protect=path)
            if n_pg > self.page_alloc.free_count():
                raise PoolExhausted(
                    f"request needs {n_pg} pool pages for "
                    f"{len(new_segs)} new node(s), only "
                    f"{self.page_alloc.free_count()} of {self.num_pages} "
                    f"free — retire first")
            state = self.release_retired(state)
        slots = free_s[:n_samples]

        total = sum(int(s.shape[1]) for s in segments)
        offset = sum(int(s.shape[1]) for s in segments[:matched])
        self.prefix_stats["admits"] += 1
        if matched:
            self.prefix_stats["partial_hits"] += 1
        self.prefix_stats["reused_tokens"] += offset
        self.prefix_stats["new_tokens"] += total - offset
        self.prefix_stats["computed_tokens"] += total - offset

        # reserve trie identity + pages for every new level; KV arrives
        # chunk by chunk, the node goes LIVE only at its last chunk.
        parent = path[-1] if path else -1
        new_nodes = []
        for seg in new_segs:
            nid = free_n.pop(0)
            m = int(seg.shape[1])
            if self.paged:
                from repro.core.paged import pages_needed

                self.node_pages[nid] = self.page_alloc.alloc(
                    pages_needed(m, tcfg.page_size))
            key = (parent, tuple(int(t) for t in jax.device_get(seg)[0]))
            self.node_index[key] = nid
            self.node_key[nid] = key
            self.node_len[nid] = m
            self.node_pending.add(nid)
            new_nodes.append((nid, m))
            path.append(nid)
            parent = nid
        for nid in path:
            self.node_refs[nid] += 1
            self.node_cached.pop(nid, None)  # revival: cached -> live

        rid = self.next_rid
        self.next_rid += 1
        self.last_rid = rid
        self.requests[rid] = {"path": list(path), "slots": list(slots),
                              "live": True}
        for s in slots:
            self.slot_request[s] = rid
            self.outputs[s] = []
            self.logps[s] = []
            self.corrupt_slots.discard(s)
        suffix = np.concatenate(
            [np.asarray(jax.device_get(s))[0] for s in new_segs])
        self._pending[rid] = {
            "path": list(path), "slots": list(slots), "matched": matched,
            "new": new_nodes, "suffix": suffix.astype(np.int32),
            "cut": offset, "done": 0, "node_i": 0, "buf_len": 0,
            "fresh_start": offset,
            # kernel path: per-layer fresh-KV envelopes (lazy); ref path:
            # accumulated suffix KV in model dtype
            "k_fresh": None, "v_fresh": None, "kbuf": None, "vbuf": None,
        }
        self._compact_requests()
        return state, slots

    def step_chunk(self, params, state: ForestState, n_steps: int):
        """Packed mode: decompose the chunk into single steps while any
        prefill is pending, piggybacking one suffix chunk per step; the
        remainder (or the whole chunk when nothing is pending) runs
        through the inherited one-dispatch scan."""
        if self.tcfg.step_mode != "packed":
            return super().step_chunk(params, state, n_steps)
        done = 0
        while done < n_steps:
            if not self._pending:
                return super().step_chunk(params, state, n_steps - done)
            state = self._packed_step(params, state)
            done += 1
        return state

    def _packed_step(self, params, state: ForestState) -> ForestState:
        """ONE packed heterogeneous step: the whole slot table advances
        one decode token AND the oldest pending prefill advances one
        suffix chunk (never crossing a trie-node boundary). Node
        completion writes the buffered KV into the cache; completing the
        last node ACTIVATES the request from the final chunk's logits."""
        import numpy as np

        rid = min(self._pending)
        pend = self._pending[rid]
        nid, m_node = pend["new"][pend["node_i"]]
        cv = min(self._prefill_chunk(), m_node - pend["buf_len"])
        chunk = pend["suffix"][pend["done"]:pend["done"] + cv]

        with span("engine.packed_step", rid=rid, prefill_tokens=cv) as sp:
            with span("engine.inputs"):
                active = np.asarray(state.active)
                if active.any():
                    deepest = int(
                        np.asarray(state.cache.dec_lens)[active].max())
                    cap = state.cache.decode_capacity
                    if deepest + 1 > cap:
                        raise DecodeCapacityExceeded(
                            f"packed step would overflow decode_capacity="
                            f"{cap} (deepest live slot at {deepest}); "
                            f"retire slots first")
            sp.set(decode_rows=int(active.sum()))
            if self.paged and self.tcfg.use_kernel:
                state, out, logits_last = self._packed_step_kernel(
                    params, state, pend, chunk, cv)
            else:
                with span("engine.dispatch"):
                    state, out, logits_last = self._packed_step_ref(
                        params, state, pend, chunk, cv)
            self.decode_dispatches += 1
            with span("engine.collect"):
                self._collect_emitted(*out)
            pend["buf_len"] += cv
            pend["done"] += cv
            if pend["buf_len"] == m_node:
                with span("engine.node_write"):
                    state = self._complete_node(state, pend, nid, m_node)
            if pend["node_i"] == len(pend["new"]):
                with span("engine.activate"):
                    state = self._activate_pending(state, rid, logits_last)
                event("request.first_token", rid=rid)
        return state

    def _packed_one_body(self, params, state: ForestState, k_fresh,
                         v_fresh, chunk_tokens, buf_len, chunk_valid,
                         fresh_start, fresh_path):
        """Jitted kernel-path packed step (compiled ONCE: every chunk of
        every admission reuses it — all chunk bookkeeping is traced
        data). Mirrors ``_decode_one`` for the decode half and returns
        the chunk's last-live-row logits for activation."""
        ecfg = self.ecfg
        cp = chunk_tokens.shape[1]
        key, sub = jax.random.split(state.key)
        fresh_pos = fresh_start + buf_len + jnp.arange(cp, dtype=jnp.int32)
        with jax.named_scope("packed_step"):
            logits, logits_c, cache, k_fresh, v_fresh = \
                self.model.decode_step_packed(
                    params, state.cache, state.tokens, chunk_tokens,
                    self.rules, k_fresh=k_fresh, v_fresh=v_fresh,
                    buf_len=buf_len, chunk_valid=chunk_valid,
                    fresh_start=fresh_start, fresh_pos=fresh_pos,
                    fresh_path=fresh_path)
        logits = logits[:, -1]
        sampled = sample_tokens(sub, logits, ecfg.temperature, ecfg.top_p)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tok_logp = jnp.take_along_axis(logp, sampled[:, None], axis=-1)[:, 0]
        emit = state.active
        tok = jnp.where(emit, sampled, ecfg.pad_token)
        active = emit & (sampled != ecfg.eos_token) if ecfg.eos_token >= 0 \
            else emit
        new = ForestState(
            cache=cache,
            tokens=tok[:, None],
            active=active,
            steps=state.steps + emit.astype(jnp.int32),
            key=key,
        )
        logits_last = logits_c[0, chunk_valid - 1]
        return new, (tok, tok_logp, emit), logits_last, k_fresh, v_fresh

    def _packed_step_kernel(self, params, state: ForestState, pend,
                            chunk, cv):
        import numpy as np

        tcfg, cfg = self.tcfg, self.cfg
        cp = self._prefill_chunk()
        with span("engine.inputs"):
            if pend["k_fresh"] is None:
                shape = (cfg.n_layers, self.pages_per_node * tcfg.page_size,
                         cfg.n_kv_heads_padded, cfg.kq_dim)
                dtype = state.cache.k_dec.dtype
                pend["k_fresh"] = jnp.zeros(shape, dtype)
                pend["v_fresh"] = jnp.zeros(shape, dtype)
            buf = np.zeros((1, cp), np.int32)
            buf[0, :cv] = chunk
            fpath = np.full((tcfg.depth,), -1, np.int32)
            fpath[:len(pend["path"])] = pend["path"]
            args = (jnp.asarray(buf), jnp.int32(pend["buf_len"]),
                    jnp.int32(cv), jnp.int32(pend["fresh_start"]),
                    jnp.asarray(fpath))
        with span("engine.dispatch"):
            state, out, logits_last, pend["k_fresh"], pend["v_fresh"] = \
                self._packed_one(params, state, pend["k_fresh"],
                                 pend["v_fresh"], *args)
        return state, out, logits_last

    def _packed_step_ref(self, params, state: ForestState, pend,
                         chunk, cv):
        """Reference packed step (dense caches / ``use_kernel=False``):
        the decode half is the inherited single-step scan — bit-identical
        to ``step_mode="decode"`` — and the chunk half composes
        ``model.prefill`` / ``model.prefill_suffix`` over [matched
        ancestors ⊕ the suffix KV buffered so far], which is row-for-row
        bit-identical to the synchronous one-shot suffix prefill (exact-
        zero causal masking makes each row independent of later rows)."""
        state, (toks, lps, emits) = self._chunk(params, state, n_steps=1)
        start = pend["cut"] + pend["done"]
        chunk_arr = jnp.asarray(chunk)[None, :]
        if start == 0:
            logits_c, cache_c = self.model.prefill(
                params, chunk_arr, self.rules)
        else:
            k_anc, v_anc = self._pending_context(state, pend, start)
            logits_c, cache_c = self.model.prefill_suffix(
                params, chunk_arr, k_anc, v_anc, self.rules, start=start)
        k_new, v_new = cache_c.k[:, 0], cache_c.v[:, 0]  # (L, cv, g, hd)
        pend["kbuf"] = (k_new if pend["kbuf"] is None
                        else jnp.concatenate([pend["kbuf"], k_new], axis=1))
        pend["vbuf"] = (v_new if pend["vbuf"] is None
                        else jnp.concatenate([pend["vbuf"], v_new], axis=1))
        return state, (toks[0], lps[0], emits[0]), logits_c

    def _pending_context(self, state: ForestState, pend, start: int):
        """The pending request's first ``start`` tokens of per-layer K/V
        in prefill layout (L, 1, start, g, hd): matched ancestors read
        from the cache ⊕ suffix tokens buffered by earlier chunks."""
        ks, vs = [], []
        if pend["cut"]:
            k_m, v_m = self._gather_path_kv(
                state, pend["path"][:pend["matched"]], pend["cut"])
            ks.append(k_m[:, 0])
            vs.append(v_m[:, 0])
        if pend["done"]:
            ks.append(pend["kbuf"])
            vs.append(pend["vbuf"])
        return (jnp.concatenate(ks, axis=1)[:, None],
                jnp.concatenate(vs, axis=1)[:, None])

    def _complete_node(self, state: ForestState, pend, nid: int,
                       m: int) -> ForestState:
        """All of node ``nid``'s tokens have been chunk-prefilled: write
        the buffered KV into the serve cache (quantize/transpose once, by
        value — same write path as synchronous admission), fingerprint
        it, and flip the node live so later chunks/requests stream it."""
        if pend["k_fresh"] is not None:
            k, v = pend["k_fresh"][:, :m], pend["v_fresh"][:, :m]
        else:
            lo = pend["done"] - m
            k = pend["kbuf"][:, lo:lo + m]
            v = pend["vbuf"][:, lo:lo + m]
        cache = state.cache
        if self.paged:
            cache = cache.write_node(k, v, nid, self.node_pages[nid])
        else:
            cache = cache.write_node(k, v, nid)
        from repro.core.integrity import segment_checksum
        self.seg_checksums[nid] = segment_checksum(cache, nid)
        self.node_live[nid] = True
        self.node_pending.discard(nid)
        pend["node_i"] += 1
        pend["buf_len"] = 0
        pend["fresh_start"] += m
        return dataclasses.replace(state, cache=cache)

    def _activate_pending(self, state: ForestState, rid: int,
                          logits0) -> ForestState:
        """The pending request's last chunk landed: point its reserved
        slots at the now-fully-live path and sample their first token
        from the final chunk's last-live-row logits — the exact analogue
        of synchronous admission's prefill-logits sampling."""
        tcfg = self.tcfg
        pend = self._pending.pop(rid)
        path, slots = pend["path"], pend["slots"]
        path_col = jnp.asarray(
            path + [-1] * (tcfg.depth - len(path)), jnp.int32)
        slot_ids = jnp.asarray(slots, jnp.int32)
        slot_mask = jnp.zeros((tcfg.slots,), bool).at[slot_ids].set(True)
        cache = state.cache.assign_paths(slot_mask, path_col)
        key, sub = jax.random.split(state.key)
        tok, lp, live = self._sample_first(sub, logits0, len(slots))
        state = ForestState(
            cache=cache,
            tokens=state.tokens.at[slot_ids, 0].set(tok),
            active=state.active.at[slot_ids].set(live),
            steps=state.steps.at[slot_ids].set(0),
            key=key,
        )
        for i, s in enumerate(slots):
            self.outputs[s] = [int(tok[i])]
            self.logps[s] = [float(lp[i])]
        return state

    def _abort_pending(self, state: ForestState, rid: int) -> ForestState:
        """Hard-abort an in-flight packed prefill (cancellation /
        preemption / deadline): UNWRITTEN reserved nodes free immediately
        — trie identity dropped, pages released, nothing was ever written
        to the cache — and matched ancestors plus already-completed nodes
        release through the same refcounted path as retirement."""
        pend = self._pending.pop(rid)
        self.requests[rid]["live"] = False
        for nid, _m in pend["new"][pend["node_i"]:]:
            self.node_pending.discard(nid)
            self.node_refs[nid] -= 1
            self.node_index.pop(self.node_key[nid], None)
            self.node_key[nid] = None
            self.node_len[nid] = 0
            if self.paged:
                self.page_alloc.release(self.node_pages.pop(nid, []))
        self._release_path(pend["path"][:pend["matched"] + pend["node_i"]])
        self._compact_requests()
        return state

    # ---- retire ----
    @spanned("engine.retire")
    def retire_requests(self, state: ForestState, active=None):
        """Free every request whose slots have all gone inactive. Node
        refcounts drop along the retired paths; a node's segment (and its
        trie-index entry) frees only at refcount zero — an ancestor shared
        with a still-live request survives. With ``prefix_cache`` on, a
        refcount-zero node is NOT freed: it transitions to the CACHED
        state (pages held, index entry kept, LRU-stamped) and frees only
        under pool pressure via ``_evict_cached``. Returns retired request
        ids; their slots become reusable by the next ``admit``.

        ``active`` optionally supplies a host snapshot of ``state.active``
        so a serve loop that already synced it this round doesn't pay a
        second device→host transfer."""
        import numpy as np

        if active is None:
            active = np.asarray(state.active)
        retired = []
        for rid in sorted(self.requests):
            req = self.requests[rid]
            if not req["live"]:
                continue
            if rid in self._pending:
                # mid-prefill: its reserved slots are inactive by
                # construction, but the request is NOT done — it retires
                # only through cancellation (_abort_pending) or after
                # activation.
                continue
            if not any(active[s] for s in req["slots"]):
                req["live"] = False
                retired.append(rid)
                self._release_path(req["path"])
        if retired:
            self._compact_requests()
        return retired

    def _release_path(self, path):
        """Drop one reference from every node on ``path`` and run the
        refcount-zero transition (children-first): with ``prefix_cache``
        the node goes live -> CACHED (row, pages, index entry and
        checksum kept, LRU-stamped); otherwise it frees outright."""
        for nid in path:
            self.node_refs[nid] -= 1
        for nid in reversed(path):
            if self.node_refs[nid] == 0 and self.node_live[nid]:
                if self.tcfg.prefix_cache:
                    # live -> cached: keep the row, the pages,
                    # the index entry and the checksum — a
                    # re-admission revives all of it for free.
                    if nid not in self.node_cached:
                        self.lru_clock += 1
                        self.node_cached[nid] = self.lru_clock
                    continue
                self.node_live[nid] = False
                self.node_index.pop(self.node_key[nid], None)
                self.node_key[nid] = None
                self.node_len[nid] = 0
                self.seg_checksums.pop(nid, None)
                if self.paged:
                    # refcounted page sharing: an ancestor's pages
                    # free only with the node itself (last
                    # referencing request gone)
                    self.page_alloc.release(
                        self.node_pages.pop(nid, []))

    def _compact_requests(self):
        """Drop retired request-table entries no slot references anymore.
        The table stays O(slots) instead of O(history); rids are
        monotonic (``next_rid``) so journal replay and ticket handles
        stay stable — a compacted rid is simply absent, and
        ``request_live`` reports it dead."""
        referenced = {rid for rid in self.slot_request if rid >= 0}
        for rid in [r for r, req in self.requests.items()
                    if not req["live"] and r not in referenced]:
            del self.requests[rid]

    @spanned("engine.release")
    def release_retired(self, state: ForestState) -> ForestState:
        """Paged mode: clear the page-table rows of every freed trie node,
        structurally removing their pages from the decode kernels'
        live-page walk (ZERO bytes for freed nodes). Live ancestors shared
        with surviving requests are untouched. Value-only update: no
        recompile. Dense mode: identity."""
        if not self.paged:
            return state
        cache = state.cache
        for nid in range(self.tcfg.n_nodes):
            if not self.node_live[nid]:
                cache = cache.free_node(nid)
        return dataclasses.replace(state, cache=cache)

    # ---- robustness surface ----
    def cancel_request(self, state: ForestState, rid: int) -> ForestState:
        """Deactivate every slot of a LIVE request (preemption / deadline /
        client cancellation). Refcounted resource release happens through
        the normal ``retire_requests`` path — shared ancestors survive; a
        preempted request re-admitted later re-matches whatever prefix is
        still resident, so re-prefill costs only the evicted suffix.
        Tolerates already-compacted rids (no-op). A request whose packed
        prefill is still in flight has no active slots to deactivate —
        its pending prefill is hard-aborted instead (unwritten nodes
        free immediately)."""
        req = self.requests.get(rid)
        if req is None or not req["live"]:
            return state
        if rid in self._pending:
            return self._abort_pending(state, rid)
        return self.deactivate_slots(state, req["slots"])

    def request_sharing(self, rid: int) -> int:
        """How many of this request's trie nodes are SHARED with another
        live request (refcount > 1). The preemption policy evicts the
        LEAST shared victim first: its nodes free the most pages (nothing
        else holds them) and its re-admission re-prefills the most cheaply
        relative to what anyone else loses."""
        req = self.requests.get(rid)
        if req is None:
            return 0
        return sum(1 for nid in req["path"] if self.node_refs[nid] > 1)

    def _live_segments(self):
        # cached nodes are RESIDENT (node_live stays True) so they remain
        # checksum- and audit-visible until actually evicted
        return [n for n in range(self.tcfg.n_nodes) if self.node_live[n]]

    def occupancy(self, state: ForestState) -> dict:
        occ = super().occupancy(state)
        occ["nodes_cached"] = len(self.node_cached)
        if self.paged:
            occ["pages_cached"] = sum(
                len(self.node_pages.get(n, ())) for n in self.node_cached)
        return occ

    def audit_state(self, state: ForestState,
                    extra_tracked: Sequence[int] = (),
                    verify_checksums: bool = False) -> bool:
        """Run ``PageAllocator.audit`` against the engine's device-side
        page tables (live nodes' rows) and host-side page mirrors.
        ``extra_tracked`` lists pages a caller holds OUTSIDE the engine
        mirrors (e.g. the frontend's fault-stolen pages) so the refcount
        <-> holder reconciliation stays exact. Dense mode has no
        allocator: allocator checks are trivially True.
        ``verify_checksums=True`` additionally re-fingerprints every live
        node's KV bytes against its write-time CRC (device round-trip per
        node — on-demand, not every round)."""
        if verify_checksums:
            self.verify_checksums(state)
        if not self.paged:
            return True
        import numpy as np

        tables = np.asarray(state.cache.store.page_tables)
        rows = [tables[n] for n in range(self.tcfg.n_nodes)
                if self.node_live[n]]
        tracked = [pid for ids in self.node_pages.values() for pid in ids]
        tracked.extend(int(i) for i in extra_tracked)
        return self.page_alloc.audit(rows=rows, tracked=tracked)

    # ---- durable-state serialization (checkpoint/recovery) ----
    def host_state(self) -> dict:
        if self._pending:
            raise RuntimeError(
                "host_state with packed prefills in flight — drain the "
                "pending chunks (step the engine) before snapshotting; "
                "in-flight fresh-KV buffers are not serializable state "
                "(DurableFrontend defers its snapshot until the engine "
                "is quiescent)")
        d = super().host_state()
        d.update({
            "node_live": [bool(x) for x in self.node_live],
            "node_refs": [int(x) for x in self.node_refs],
            # (parent, token tuple) keys flattened for JSON; node_key is
            # the exact inverse and is rebuilt on load
            "node_index": [[int(parent), [int(t) for t in toks], int(nid)]
                           for (parent, toks), nid
                           in self.node_index.items()],
            "slot_request": [int(x) for x in self.slot_request],
            # requests as (rid, entry) pairs: the table is a compacted
            # dict keyed by stable monotonic rids, NOT a dense list
            "requests": [[int(rid),
                          {"path": [int(n) for n in r["path"]],
                           "slots": [int(s) for s in r["slots"]],
                           "live": bool(r["live"])}]
                         for rid, r in sorted(self.requests.items())],
            "next_rid": int(self.next_rid),
            "node_len": [int(x) for x in self.node_len],
            # cached-node set + LRU clock survive snapshot/replay so
            # post-recovery eviction order is bit-identical
            "node_cached": [[int(n), int(stamp)] for n, stamp
                            in sorted(self.node_cached.items())],
            "lru_clock": int(self.lru_clock),
            "prefix_stats": {k: int(v)
                             for k, v in self.prefix_stats.items()},
        })
        if self.paged:
            d["node_pages"] = [[int(n), [int(p) for p in ids]]
                               for n, ids in self.node_pages.items()]
            d["allocator"] = self.page_alloc.state_dict()
        return d

    def load_host_state(self, d: dict):
        super().load_host_state(d)
        self.node_live = [bool(x) for x in d["node_live"]]
        self.node_refs = [int(x) for x in d["node_refs"]]
        self.node_index = {}
        self.node_key = [None] * self.tcfg.n_nodes
        for parent, toks, nid in d["node_index"]:
            key = (int(parent), tuple(int(t) for t in toks))
            self.node_index[key] = int(nid)
            self.node_key[int(nid)] = key
        self.slot_request = [int(x) for x in d["slot_request"]]
        self.requests = {int(rid): {"path": [int(n) for n in r["path"]],
                                    "slots": [int(s) for s in r["slots"]],
                                    "live": bool(r["live"])}
                         for rid, r in d["requests"]}
        self.next_rid = int(d["next_rid"])
        self.last_rid = self.next_rid - 1
        self.node_len = [int(x) for x in d["node_len"]]
        self.node_cached = {int(n): int(stamp)
                            for n, stamp in d["node_cached"]}
        self.lru_clock = int(d["lru_clock"])
        self.prefix_stats = {k: int(v)
                             for k, v in d["prefix_stats"].items()}
        if self.paged:
            self.node_pages = {int(n): [int(p) for p in ids]
                               for n, ids in d["node_pages"]}
            self.page_alloc.load_state_dict(d["allocator"])
        return self
