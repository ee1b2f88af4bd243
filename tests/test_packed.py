"""Packed heterogeneous-step kernel + ``step_mode="packed"`` serving.

Fast (kernel/queue) tier:
  * work-queue builder edge cases — empty queue, FREE-segment exclusion,
    single-row prefill chunk, ragged chunk bias, fresh-tile positions —
    and ZERO recompiles across all of them (admissions, retirements and
    chunk growth are runtime data under one compiled envelope);
  * structural streamed-tile counts: the queue streams exactly the live
    pages + ceil(fresh_len/pm) fresh tiles, never dead capacity, and the
    pinned tail never issues a DMA;
  * no-HBM-spill for the packed kernels (bf16 + q8 with a chunk
    attached — the fresh K/V envelopes are the two extra full-dtype
    operands allowed by design);
  * chunk-carrying multi-launch chaining bit-identical to single-launch;
  * the chunk half against a NumPy causal oracle over
    [ancestor pages ⊕ fresh tiles].

Engine (slow) tier:
  * ISSUE acceptance: greedy serve tokens with ``step_mode="packed"``
    BIT-IDENTICAL to ``step_mode="decode"`` across tree x {dense, paged}
    x {bf16, int8} on the reference path (chunked suffix prefill is
    row-for-row exact: masked columns underflow to exactly 0.0);
  * kernel path: bf16 greedy tokens identical; int8 chunk logits within
    reduction-order tolerance of the reference path (online softmax over
    pages vs single-pass — argmax near-ties may flip, same class as the
    documented kernel/einsum divergence);
  * pending-prefill lifecycle: PrefillInFlight on colliding admissions,
    clean abort via cancel_request, host_state guarded while pending,
    packed step compiles ONCE across admits/chunks/activations.

(Decode-only bit-identity to the paged kernel and the 13-impl
cross-check live in tests/test_differential.py.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (
    assert_no_hbm_spill,
    build_page_pool,
    make_decode_case,
    pallas_grids,
)
from repro.configs import TreeConfig, get_config, reduced_config
from repro.core.errors import PrefillInFlight
from repro.core.quantized import quantize_ctx
from repro.kernels.bifurcated_decode import walk_vmem_bytes
from repro.kernels.ops import (
    packed_bifurcated_decode_attention,
    packed_bifurcated_decode_attention_q8,
    packed_work_queue,
)
from repro.models import get_model
from repro.runtime.serve import TreeServeEngine

G, HD = 2, 32


# ---------------------------------------------------------------------------
# Work-queue builder: edge cases + zero recompiles (satellite)
# ---------------------------------------------------------------------------

def _queue(seg_lens, tables, pm=8, fresh_len=0, fresh_start=0, fcap=2):
    return packed_work_queue(
        jnp.asarray(tables, jnp.int32), jnp.asarray(seg_lens, jnp.int32),
        pm, fresh_len=jnp.int32(fresh_len),
        fresh_start=jnp.int32(fresh_start), num_fresh_tiles=fcap,
        pseudo_seg=len(seg_lens))


def test_packed_queue_empty():
    """All segments free, no chunk: n_ent == 0 — the grid's early-exit
    envelope streams nothing."""
    kind, seg, pdma, fdma, pos, n_ent, bias = _queue(
        [0, 0, 0], [[-1, -1]] * 3)
    assert int(n_ent[0]) == 0


def test_packed_queue_free_segment_exclusion():
    """FREE segments (len 0) and unallocated table rows contribute no
    entries; live pages keep the paged kernels' (segment, page) order."""
    kind, seg, pdma, fdma, pos, n_ent, bias = _queue(
        [13, 0, 8], [[4, 5, -1], [-1, -1, -1], [2, -1, -1]], pm=8, fcap=1)
    ne = int(n_ent[0])
    assert ne == 3                       # ceil(13/8)=2 + 0 + 1
    np.testing.assert_array_equal(np.asarray(pdma)[:ne], [4, 5, 2])
    np.testing.assert_array_equal(np.asarray(seg)[:ne], [0, 0, 2])
    # ragged tail of segment 0: page 5 keeps only 13 - 8 = 5 live columns
    tail = np.asarray(bias)[1]
    assert (tail[:5] == 0).all() and (tail[5:] < -1e29).all()


def test_packed_queue_single_row_chunk():
    """A 1-token prefill chunk enqueues exactly one fresh tile whose bias
    masks every column past the first, positioned at fresh_start."""
    kind, seg, pdma, fdma, pos, n_ent, bias = _queue(
        [8], [[3]], pm=8, fresh_len=1, fresh_start=21, fcap=2)
    ne = int(n_ent[0])
    assert ne == 2 and int(kind[1]) == 1
    assert int(seg[1]) == 1              # pseudo-segment id == n_seg
    assert int(pos[1]) == 21
    row = np.asarray(bias)[1]
    assert row[0] == 0 and (row[1:] < -1e29).all()


def test_packed_queue_fresh_tile_positions():
    """Multi-tile chunks advance ent_pos by pm per tile and split the
    ragged tail bias at fresh_len."""
    kind, seg, pdma, fdma, pos, n_ent, bias = _queue(
        [8], [[0]], pm=8, fresh_len=13, fresh_start=40, fcap=2)
    ne = int(n_ent[0])
    assert ne == 3
    np.testing.assert_array_equal(np.asarray(kind)[:ne], [0, 1, 1])
    np.testing.assert_array_equal(np.asarray(pos)[1:ne], [40, 48])
    np.testing.assert_array_equal(np.asarray(fdma)[1:ne], [0, 1])
    tail = np.asarray(bias)[2]
    assert (tail[:5] == 0).all() and (tail[5:] < -1e29).all()


def test_packed_queue_streamed_tiles():
    """Structural: within n_ent every entry advances a DMA stream exactly
    once (live pages + fresh tiles); the pinned tail past n_ent revisits
    the same block index, so by the revisit rule it streams NOTHING."""
    kind, seg, pdma, fdma, pos, n_ent, bias = _queue(
        [13, 0, 8], [[4, 5, -1], [-1, -1, -1], [2, -1, -1]],
        pm=8, fresh_len=9, fresh_start=25, fcap=2)
    ne = int(n_ent[0])
    kind, pdma, fdma = (np.asarray(kind), np.asarray(pdma),
                        np.asarray(fdma))
    # interleave the two streams exactly as the grid sees them
    page_stream = [int(pdma[i]) for i in range(ne) if kind[i] == 0]
    fresh_stream = [int(fdma[i]) for i in range(ne) if kind[i] == 1]
    n_page_dma = 1 + int(np.sum(np.asarray(page_stream)[1:]
                                != np.asarray(page_stream)[:-1]))
    n_fresh_dma = 1 + int(np.sum(np.asarray(fresh_stream)[1:]
                                 != np.asarray(fresh_stream)[:-1]))
    assert n_page_dma == 3               # pages 4, 5 (revisited), 2
    assert n_fresh_dma == 2              # tiles 0, 1
    # pinned tail: both streams hold their last index past n_ent
    assert (pdma[ne:] == pdma[ne - 1] if kind[ne - 1] == 0
            else pdma[ne:] == pdma[ne:][0]).all()
    assert (fdma[ne:] == fdma[ne - 1]).all()


def test_packed_queue_zero_recompiles():
    """Satellite acceptance: empty queue, single-row chunk, free-segment
    churn and chunk growth all reuse ONE compiled queue builder — every
    input is traced data under a fixed shape envelope."""
    pm, fcap = 8, 2
    jitted = jax.jit(lambda t, sl, fl, fs: packed_work_queue(
        t, sl, pm, fresh_len=fl, fresh_start=fs,
        num_fresh_tiles=fcap, pseudo_seg=3))
    tables = jnp.asarray([[4, 5, -1], [-1, -1, -1], [2, -1, -1]], jnp.int32)
    variants = [
        ([0, 0, 0], 0, 0),               # empty
        ([17, 0, 8], 0, 0),              # decode-only
        ([17, 0, 8], 1, 21),             # single-row chunk
        ([17, 0, 8], 13, 21),            # multi-tile chunk
        ([8, 0, 0], 16, 8),              # retirement churn, full tiles
    ]
    for sl, fl, fs in variants:
        jitted(tables, jnp.asarray(sl, jnp.int32),
               jnp.int32(fl), jnp.int32(fs))
    assert jitted._cache_size() == 1


def test_packed_dispatch_zero_recompiles():
    """The full packed dispatcher compiles ONCE across decode-only,
    single-row-chunk and multi-tile-chunk steps of the same envelope."""
    case = make_decode_case(3, 1, 24, 4, g=G, hd=HD, dtype=jnp.bfloat16)
    pm = 8
    pad = lambda x: jnp.pad(x, ((0, 0), (0, 0)) + ((0, 0),) * (x.ndim - 2))
    kc = case["kc"].transpose(1, 0, 2)[None]          # (1, g, 24, hd)
    vc = case["vc"].transpose(1, 0, 2)[None]
    (kp, vp), table = build_page_pool([kc, vc], [24], pm)
    seg_lens = jnp.asarray([24], jnp.int32)
    paths = jnp.zeros((1, 3), jnp.int32)
    rng = np.random.RandomState(3)
    qf = jnp.asarray(rng.randn(4, G, 1, HD), jnp.bfloat16)
    kf = jnp.asarray(rng.randn(2 * pm, G, HD), jnp.bfloat16)
    vf = jnp.asarray(rng.randn(2 * pm, G, HD), jnp.bfloat16)

    before = packed_bifurcated_decode_attention._cache_size()
    for fl, fp0 in [(0, -1), (1, 24), (9, 24)]:
        fpos = jnp.where(jnp.arange(4) < max(fl, 1) - 0,
                         fp0 + jnp.arange(4), -1).astype(jnp.int32)
        packed_bifurcated_decode_attention(
            case["q"], kp, vp, table, seg_lens, paths,
            case["kd"], case["vd"], case["mask"],
            q_fresh=qf, k_fresh=kf, v_fresh=vf,
            fresh_len=jnp.int32(fl), fresh_start=jnp.int32(24),
            fresh_pos=fpos, fresh_path=jnp.asarray([0], jnp.int32),
            interpret=True)
    assert packed_bifurcated_decode_attention._cache_size() == before + 1


# ---------------------------------------------------------------------------
# Packed kernel: chunk oracle, no-spill, multi-launch with a chunk
# ---------------------------------------------------------------------------

def _chunk_case(seed=0, m_anc=24, cp=6, buf=3, b=2, c_d=4, pm=8, fcap=2,
                g=G):
    """One packed step mid-prefill: b decode rows over the ancestor
    segment + a cp-row chunk at absolute offset m_anc + buf whose fresh
    envelope holds buf + cp live columns."""
    rng = np.random.RandomState(seed)
    f = lambda *s: rng.randn(*s).astype(np.float32)
    case = {
        "q": jnp.asarray(f(b, g, 1, 1, HD), jnp.float32),
        "kd": jnp.asarray(f(b, c_d, g, HD), jnp.float32),
        "vd": jnp.asarray(f(b, c_d, g, HD), jnp.float32),
        "mask": jnp.ones((b, c_d), bool),
        "kc": jnp.asarray(f(m_anc, g, HD), jnp.float32),
        "vc": jnp.asarray(f(m_anc, g, HD), jnp.float32),
    }
    fresh_len = buf + cp
    kf_live = f(fresh_len, g, HD)
    vf_live = f(fresh_len, g, HD)
    kf = np.zeros((fcap * pm, g, HD), np.float32)
    vf = np.zeros_like(kf)
    kf[:fresh_len], vf[:fresh_len] = kf_live, vf_live
    case.update(
        q_fresh=jnp.asarray(f(cp, g, 1, HD), jnp.float32),
        k_fresh=jnp.asarray(kf), v_fresh=jnp.asarray(vf),
        fresh_len=fresh_len, fresh_start=m_anc,
        fresh_pos=jnp.asarray(m_anc + buf + np.arange(cp), jnp.int32),
        pm=pm)
    return case


def _pool(case, q8=False):
    m_anc, pm = case["kc"].shape[0], case["pm"]
    kc = np.asarray(case["kc"]).transpose(1, 0, 2)[None]
    vc = np.asarray(case["vc"]).transpose(1, 0, 2)[None]
    if q8:
        kq, ks = quantize_ctx(jnp.asarray(kc[0]), fold_scale=HD**-0.5)
        vq, vs = quantize_ctx(jnp.asarray(vc[0]))
        arrays = [np.asarray(kq)[None], np.asarray(vq)[None],
                  np.asarray(ks)[None], np.asarray(vs)[None]]
    else:
        arrays = [kc, vc]
    return build_page_pool(arrays, [m_anc], pm, perm_seed=5)


def _chunk_oracle(case):
    """NumPy single-pass softmax for each chunk row over
    [ancestors ⊕ causally-visible fresh columns]."""
    cp, g_all = case["q_fresh"].shape[:2]
    m_anc, fl = case["fresh_start"], case["fresh_len"]
    scale = HD**-0.5
    out = np.zeros((cp, g_all, 1, HD), np.float32)
    kc, vc = np.asarray(case["kc"]), np.asarray(case["vc"])
    kf = np.asarray(case["k_fresh"])[:fl]
    vf = np.asarray(case["v_fresh"])[:fl]
    K = np.concatenate([kc, kf])        # (m_anc + fl, G, HD)
    V = np.concatenate([vc, vf])
    pos = np.concatenate([np.full(m_anc, -1), m_anc + np.arange(fl)])
    for i in range(cp):
        rp = int(case["fresh_pos"][i])
        vis = pos <= rp
        for g in range(g_all):
            qi = np.asarray(case["q_fresh"])[i, g, 0]
            s = (K[vis, g] @ qi) * scale
            w = np.exp(s - s.max())
            w /= w.sum()
            out[i, g, 0] = w @ V[vis, g]
    return out


# kv heads x the head block the VMEM budget is cut to (None: not cut). The
# packed kernel walks all g heads per grid step at these shapes; cut to
# fit two, 8 heads take four blocks.
HEAD_BLOCKS = pytest.mark.parametrize("g,budget_hb", [
    (G, None), (1, None), (8, None), (8, 2),
], ids=["g2", "g1", "g8", "g8-budget-hb2"])


def _cut_budget(walk_budget, case, hb, **kw):
    """Budget for which a single packed launch over ``case`` walks ``hb``
    heads per step (chained launches, which hold more, walk fewer)."""
    cp = case["q_fresh"].shape[0]
    rows = case["q"].shape[0] + cp
    walk_budget(walk_vmem_bytes(
        hb, rows=rows, hd=HD, pm=case["pm"], depth=2, ld=128,
        itemsize=case["q"].dtype.itemsize, fresh=True, **kw))


def _chunk_kwargs(case):
    return dict(
        q_fresh=case["q_fresh"], k_fresh=case["k_fresh"],
        v_fresh=case["v_fresh"], fresh_len=jnp.int32(case["fresh_len"]),
        fresh_start=jnp.int32(case["fresh_start"]),
        fresh_pos=case["fresh_pos"],
        fresh_path=jnp.asarray([0], jnp.int32), interpret=True)


@HEAD_BLOCKS
def test_packed_chunk_matches_oracle(walk_budget, g, budget_hb):
    case = _chunk_case(g=g)
    (kp, vp), table = _pool(case)
    seg_lens = jnp.asarray([case["kc"].shape[0]], jnp.int32)
    paths = jnp.zeros((1, 2), jnp.int32)
    args = (case["q"], kp, vp, table, seg_lens, paths,
            case["kd"], case["vd"], case["mask"])
    hb = g
    if budget_hb is not None:
        _cut_budget(walk_budget, case, budget_hb)
        hb = budget_hb
    envelope = table.size + case["k_fresh"].shape[0] // case["pm"]
    assert pallas_grids(packed_bifurcated_decode_attention, *args,
                        **_chunk_kwargs(case)) == [(g // hb, envelope + 1)]
    _, out_fresh = packed_bifurcated_decode_attention(
        *args, **_chunk_kwargs(case))
    np.testing.assert_allclose(np.asarray(out_fresh), _chunk_oracle(case),
                               rtol=2e-5, atol=2e-5)


@HEAD_BLOCKS
def test_packed_multi_launch_with_chunk_bit_identical(walk_budget, g,
                                                      budget_hb):
    """Chained launches that SPLIT the queue mid-chunk (pages in one
    launch, fresh tiles in the next) reproduce the single launch
    bit-for-bit — raw fp32 state round-trips losslessly. Under a cut
    budget the chained launches, carry and all, walk fewer heads per
    step than the single launch (all g) they are compared with."""
    case = _chunk_case(m_anc=24, cp=6, buf=3, g=g)
    (kp, vp), table = _pool(case)
    seg_lens = jnp.asarray([24], jnp.int32)
    paths = jnp.zeros((1, 2), jnp.int32)
    args = (case["q"], kp, vp, table, seg_lens, paths,
            case["kd"], case["vd"], case["mask"])
    kw = _chunk_kwargs(case)
    one = packed_bifurcated_decode_attention(*args, **kw)
    if budget_hb is not None:
        _cut_budget(walk_budget, case, budget_hb, carry=True)
        grids = pallas_grids(packed_bifurcated_decode_attention, *args,
                             entries_per_launch=2, **kw)
        assert len(grids) == 3                      # 5 entries, 2 a launch
        assert all(gk > 1 for gk, _ in grids), grids   # hb < g in each
        assert grids[-1][0] == g // budget_hb, grids
    two = packed_bifurcated_decode_attention(
        *args, entries_per_launch=2, **kw)
    for a, b in zip(one, two):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_no_hbm_spill_bf16():
    case = _chunk_case()
    bf = lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x
    (kp, vp), table = _pool(case)
    seg_lens = jnp.asarray([24], jnp.int32)
    paths = jnp.zeros((1, 2), jnp.int32)

    def run(q, kp, vp, kd, vd, qf, kf, vf):
        return packed_bifurcated_decode_attention(
            q, kp, vp, table, seg_lens, paths, kd, vd, case["mask"],
            q_fresh=qf, k_fresh=kf, v_fresh=vf,
            fresh_len=jnp.int32(case["fresh_len"]),
            fresh_start=jnp.int32(case["fresh_start"]),
            fresh_pos=case["fresh_pos"],
            fresh_path=jnp.asarray([0], jnp.int32), interpret=True)

    jaxpr = jax.make_jaxpr(run)(
        bf(case["q"]), bf(kp), bf(vp), bf(case["kd"]), bf(case["vd"]),
        bf(case["q_fresh"]), bf(case["k_fresh"]), bf(case["v_fresh"]))
    assert_no_hbm_spill(jaxpr, out_dtype=jnp.bfloat16)


def test_packed_no_hbm_spill_q8():
    """q8 with a chunk attached: context K/V enter as int8 only; the
    float hd-carrying operands are exactly q + bf16 decode arm + bf16
    fresh K/V (5) — no dequantized buffer ever reaches HBM."""
    case = _chunk_case()
    bf = lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x
    (kp, vp, ksp, vsp), table = _pool(case, q8=True)
    seg_lens = jnp.asarray([24], jnp.int32)
    paths = jnp.zeros((1, 2), jnp.int32)

    def run(q, kd, vd, qf, kf, vf):
        return packed_bifurcated_decode_attention_q8(
            q, kp, vp, ksp, vsp, table, seg_lens, paths,
            kd, vd, case["mask"],
            q_fresh=qf, k_fresh=kf, v_fresh=vf,
            fresh_len=jnp.int32(case["fresh_len"]),
            fresh_start=jnp.int32(case["fresh_start"]),
            fresh_pos=case["fresh_pos"],
            fresh_path=jnp.asarray([0], jnp.int32), interpret=True)

    jaxpr = jax.make_jaxpr(run)(
        bf(case["q"]), bf(case["kd"]), bf(case["vd"]),
        bf(case["q_fresh"]), bf(case["k_fresh"]), bf(case["v_fresh"]))
    assert_no_hbm_spill(jaxpr, out_dtype=jnp.bfloat16, hd=HD, q8=True,
                        fresh=True)


# ---------------------------------------------------------------------------
# Engine tier: step_mode="packed" end-to-end
# ---------------------------------------------------------------------------

pytest.importorskip("jax")

CFG = reduced_config(get_config("internlm2-1.8b"))
MODEL = get_model(CFG)
PARAMS = MODEL.init(jax.random.PRNGKey(0))
RNG = np.random.RandomState(0)
SYS = jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 12)))
TPL = jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 6)))
REQ_A = jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 9)))
REQ_B = jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 7)))


def _engine(step_mode, **kw):
    tcfg = TreeConfig(**{**dict(
        n_nodes=8, depth=3, slots=6, node_capacity=32, decode_capacity=16,
        temperature=0.0, suffix_prefill=True, prefill_chunk=5,
        step_mode=step_mode), **kw})
    return TreeServeEngine(MODEL, CFG, tcfg)


def _serve(step_mode, spy=None, **kw):
    """Shared workload: a fresh 2-level admission, 6 steps, then a
    PARTIALLY-MATCHED 3-level admission mid-stream, 8 more steps."""
    eng = _engine(step_mode, **kw)
    if spy is not None:
        orig = eng._activate_pending

        def wrap(state, rid, logits0):
            spy.append(np.asarray(logits0, np.float32).ravel())
            return orig(state, rid, logits0)

        eng._activate_pending = wrap
    st = eng.init_state()
    st, sa = eng.admit(PARAMS, st, [SYS, REQ_A], 2)
    st = eng.step_chunk(PARAMS, st, 6)
    st, sb = eng.admit(PARAMS, st, [SYS, TPL, REQ_B], 2)
    st = eng.step_chunk(PARAMS, st, 8)
    return eng, st, sa, sb


@pytest.mark.slow
@pytest.mark.parametrize("ctx_store", ["dense", "paged"])
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
def test_packed_serve_bit_identical_to_decode(ctx_store, cache_dtype):
    """ISSUE acceptance: greedy serve tokens with ``step_mode="packed"``
    are BIT-IDENTICAL to ``step_mode="decode"`` (chunk steps displace
    decode steps, so the packed run's output stream is a prefix of the
    decode run's at equal step counts)."""
    kw = dict(ctx_store=ctx_store, cache_dtype=cache_dtype)
    de, _, da, db = _serve("decode", **kw)
    pe, _, pa, pb = _serve("packed", **kw)
    assert not pe._pending
    for sd, sp in zip(da + db, pa + pb):
        od, op = de.outputs[sd], pe.outputs[sp]
        assert len(op) >= 2
        assert od[:len(op)] == op, (sd, od, op)
        np.testing.assert_allclose(de.logps[sd][:len(op)], pe.logps[sp],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_packed_serve_kernel_bf16_greedy_identical():
    """Kernel path (paged + use_kernel): bf16 greedy tokens match the
    decode-mode run on this workload."""
    kw = dict(ctx_store="paged", use_kernel=True)
    de, _, da, db = _serve("decode", **kw)
    pe, _, pa, pb = _serve("packed", **kw)
    assert not pe._pending
    for sd, sp in zip(da + db, pa + pb):
        od, op = de.outputs[sd], pe.outputs[sp]
        assert len(op) >= 2 and od[:len(op)] == op


@pytest.mark.slow
def test_packed_serve_kernel_q8_logits_close():
    """int8 kernel path: the packed kernel's chunk logits agree with the
    reference path within reduction-order tolerance (online softmax over
    pages + dot-then-scale dequant vs single-pass einsum). Greedy argmax
    may flip on near-ties, so the gate is on logits, not tokens."""
    ref_logits, ker_logits = [], []
    _serve("packed", spy=ref_logits, ctx_store="paged", cache_dtype="int8",
           use_kernel=False)
    eng, _, pa, pb = _serve("packed", spy=ker_logits, ctx_store="paged",
                            cache_dtype="int8", use_kernel=True)
    assert not eng._pending
    assert len(ref_logits) == len(ker_logits) == 2
    for a, b in zip(ref_logits, ker_logits):
        scale = max(float(np.abs(a).max()), 1.0)
        np.testing.assert_allclose(a, b, atol=0.1 * scale)
    # the packed kernel engine still compiled its step exactly once
    assert eng._packed_one._cache_size() == 1


@pytest.mark.slow
def test_packed_prefill_in_flight_and_drain():
    """A second admission whose first NEW segment collides with a node
    still being prefilled raises the retryable PrefillInFlight; once the
    pending chunks land, the same admission succeeds and REUSES the now
    live node (no duplicate trie level)."""
    eng = _engine("packed", ctx_store="paged")
    st = eng.init_state()
    st, sa = eng.admit(PARAMS, st, [SYS, REQ_A], 1)
    assert eng._pending and eng.node_pending
    with pytest.raises(PrefillInFlight) as ei:
        eng.admit(PARAMS, st, [SYS, REQ_B], 1)
    assert ei.value.retryable and ei.value.reason == "prefill_in_flight"
    st = eng.step_chunk(PARAMS, st, 6)       # drain SYS(12)+REQ_A(9) @ 5
    assert not eng._pending and not eng.node_pending
    before = len(eng.free_nodes())
    st, sb = eng.admit(PARAMS, st, [SYS, REQ_B], 1)
    st = eng.step_chunk(PARAMS, st, 4)
    assert len(eng.free_nodes()) == before - 1   # SYS node reused
    assert eng.outputs[sb[0]]


@pytest.mark.slow
def test_packed_abort_pending_and_host_state_guard():
    """cancel_request mid-prefill rolls the reservation back — pending
    nodes freed, pages released, trie index clean — and host_state is
    guarded while a prefill is in flight."""
    eng = _engine("packed", ctx_store="paged")
    st = eng.init_state()
    free0 = len(eng.free_nodes())
    pages0 = eng.page_alloc.free_count()
    st, sa = eng.admit(PARAMS, st, [SYS, REQ_A], 1)
    rid = eng.last_rid
    with pytest.raises(RuntimeError):
        eng.host_state()
    st = eng.cancel_request(st, rid)
    assert not eng._pending and not eng.node_pending
    assert len(eng.free_nodes()) == free0
    assert eng.page_alloc.free_count() == pages0
    assert eng.audit_state(st)
    # engine still serves after the abort
    st, sb = eng.admit(PARAMS, st, [SYS, REQ_B], 1)
    st = eng.step_chunk(PARAMS, st, 6)
    assert not eng._pending and eng.outputs[sb[0]]
    eng.host_state()                         # quiescent: guard lifted
