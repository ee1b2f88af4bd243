"""Paged KV storage substrate (core/paged.py + the paged page-walk kernels).

Covers the tentpole acceptance criteria beyond the differential-harness
cross-checks (which live in tests/test_differential.py):

  * the page-pool stores (bf16 + int8): write/clear roundtrips through
    shuffled pool pages, dense materialization, allocator bookkeeping;
  * paged kernels BIT-IDENTICAL to the dense tree kernels on the same
    logical contents (ragged lengths, permuted pages, FREE nodes — both
    dtypes) and within oracle tolerance of the concatenated-context
    reference;
  * STRUCTURAL DMA elision: the live-page list streams exactly
    sum(ceil(len/page_m)) context blocks — FREE segments and dead tails
    contribute none, and clearing a segment shrinks the stream (the dense
    grid streams the full capacity envelope regardless);
  * the fused no-HBM-spill contract (one pallas_call, output-only) and
    the q8 no-dequant guarantee hold for the paged kernels;
  * paged cache families: spec/init parity, slot wipes, decode-step
    dispatch (einsum escape hatch == kernel), sharding pspecs;
  * engines under ctx_store="paged": greedy tokens identical to the dense
    engines, admission REJECTION (capacity + pool exhaustion), page
    refcounts across trie reuse/retire, decode compiles ONCE across
    admit/retire/readmit, and release_retired structurally shrinking the
    page stream;
  * core.io_model.paged_decode_io_bytes: page-rounded live bytes, free
    nodes at zero, the dense envelope recovered.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_no_hbm_spill, build_page_pool
from repro.core.paged import (
    PageAllocator,
    PagedBifurcatedCache,
    PagedGroupedBifurcatedCache,
    PagedKVStore,
    PagedPrefixTreeCache,
    QuantPagedKVStore,
    gather_pages,
    pages_needed,
)
from repro.core.quantized import quantize_ctx
from repro.kernels.ops import (
    live_page_list,
    paged_bifurcated_decode_attention,
    paged_bifurcated_decode_attention_q8,
    tree_bifurcated_decode_attention,
    tree_bifurcated_decode_attention_q8,
)

G, HD, PM = 2, 32, 64


# ---------------------------------------------------------------------------
# Case builder: one ragged trie in BOTH dense-segment and page-pool form
# ---------------------------------------------------------------------------

def make_paged_trie(node_lens, paths_cols, *, b=None, c_d=8, page_m=PM,
                    node_capacity=None, seed=0, dtype=jnp.bfloat16,
                    extra_pages=2):
    """Build one decode problem over a ragged trie twice: dense "gmk" node
    segments (zero-padded to capacity) and a page pool holding the SAME
    logical contents on shuffled pool pages (conftest.build_page_pool)."""
    rng = np.random.RandomState(seed)
    n_nodes = len(node_lens)
    node_capacity = node_capacity or max(
        pages_needed(m, page_m) for m in node_lens) * page_m
    cap = pages_needed(node_capacity, page_m) * page_m
    b = b or len(paths_cols)

    kc = np.zeros((n_nodes, G, cap, HD), np.float32)
    vc = np.zeros_like(kc)
    for i, m in enumerate(node_lens):
        kc[i, :, :m] = rng.randn(G, m, HD)
        vc[i, :, :m] = rng.randn(G, m, HD)
    kc, vc = jnp.asarray(kc, dtype), jnp.asarray(vc, dtype)
    # q8 twins: quantize the DENSE segments, then page values + scales
    kq, ks = quantize_ctx(kc, fold_scale=HD**-0.5)
    vq, vs = quantize_ctx(vc)
    (kp, vp, kpq, vpq, ksp, vsp), tables = build_page_pool(
        [kc, vc, kq, vq, ks, vs], node_lens, page_m,
        perm_seed=seed, extra_pages=extra_pages)

    case = {
        "kc": kc, "vc": vc, "kp": kp, "vp": vp,
        "kq": kq, "vq": vq, "ks": ks, "vs": vs,
        "kpq": kpq, "vpq": vpq, "ksp": ksp, "vsp": vsp,
        "tables": tables,
        "nlens": jnp.asarray(node_lens, jnp.int32),
        "q": jnp.asarray(rng.randn(b, G, 1, 1, HD), dtype),
        "kd": jnp.asarray(rng.randn(b, c_d, G, HD), dtype),
        "vd": jnp.asarray(rng.randn(b, c_d, G, HD), dtype),
        "mask": jnp.arange(c_d)[None, :] < jnp.asarray(
            rng.randint(1, c_d + 1, size=(b,)))[:, None],
        "page_m": page_m, "cap": cap,
    }
    depth = max(len(p) for p in paths_cols)
    table = np.full((depth, b), -1, np.int64)
    for s, pth in enumerate(paths_cols):
        table[:len(pth), s] = pth
    case["paths"] = jnp.asarray(table, jnp.int32)
    return case


RAGGED = dict(node_lens=[160, 37, 96, 0],          # node 3 FREE
              paths_cols=[(0,), (0, 1), (0, 2), (1,), (0, 1)])


# ---------------------------------------------------------------------------
# Stores + allocator
# ---------------------------------------------------------------------------

def test_store_write_roundtrip_shuffled_pages():
    st = PagedKVStore.init(2, 3, 4, 10, G, HD, page_m=8)
    rng = np.random.RandomState(0)
    k = jnp.asarray(rng.randn(2, 19, G, HD), jnp.bfloat16)
    v = jnp.asarray(rng.randn(2, 19, G, HD), jnp.bfloat16)
    st = st.write_segment(k, v, 1, [7, 2, 9])      # 19 tokens -> 3 pages
    kd, vd = st.dense_ctx()
    ref = k.transpose(0, 2, 1, 3)                  # (L, g, m, hd)
    assert bool(jnp.all(kd[:, 1, :, :19] == ref))
    assert float(jnp.max(jnp.abs(kd[:, 1, :, 19:]))) == 0   # page tail zero
    assert float(jnp.max(jnp.abs(kd[:, 0]))) == 0           # others intact
    assert int(st.seg_lens[1]) == 19
    np.testing.assert_array_equal(np.asarray(st.page_tables[1]),
                                  [7, 2, 9, -1])
    st = st.clear_segment(1)
    assert int(st.seg_lens[1]) == 0
    assert int(jnp.max(st.page_tables[1])) == -1


def test_quant_store_roundtrip_and_scale_fold():
    st = QuantPagedKVStore.init(1, 2, 4, 8, G, HD, page_m=8)
    rng = np.random.RandomState(1)
    k = jnp.asarray(rng.randn(1, 21, G, HD), jnp.float32)
    v = jnp.asarray(rng.randn(1, 21, G, HD), jnp.float32)
    st = st.write_segment(k, v, 0, [5, 0, 3])
    kq, vq, ks, vs = st.dense_ctx()
    ref = k.transpose(0, 2, 1, 3)
    # k scales carry hd**-0.5 pre-folded (the dense families' contract)
    deq = kq[:, 0, :, :21].astype(jnp.float32) * ks[:, 0, :, :21, None] \
        * (HD**0.5)
    assert float(jnp.max(jnp.abs(deq - ref))) < 0.05
    deqv = vq[:, 0, :, :21].astype(jnp.float32) * vs[:, 0, :, :21, None]
    assert float(jnp.max(jnp.abs(deqv - v.transpose(0, 2, 1, 3)))) < 0.05


def test_store_rejects_overflow_and_bad_page_count():
    st = PagedKVStore.init(1, 2, 2, 8, G, HD, page_m=8)   # cap 16 tokens
    k = jnp.ones((1, 17, G, HD), jnp.bfloat16)
    with pytest.raises(ValueError, match="segment capacity"):
        st.write_segment(k, k, 0, [0, 1, 2])
    k = jnp.ones((1, 12, G, HD), jnp.bfloat16)
    with pytest.raises(ValueError, match="page ids"):
        st.write_segment(k, k, 0, [0])                    # needs 2 pages


def test_page_allocator_refcounts_and_exhaustion():
    al = PageAllocator(4)
    a = al.alloc(3)
    assert al.free_count() == 1
    al.share(a[:1])
    assert al.release(a[:1]) == []          # still referenced
    assert al.release(a) == a               # refcounts hit zero in order
    assert al.free_count() == 4
    al.alloc(4)
    with pytest.raises(RuntimeError, match="exhausted"):
        al.alloc(1)


# ---------------------------------------------------------------------------
# Structural DMA elision: the live-page list IS the context stream
# ---------------------------------------------------------------------------

def test_live_page_list_streams_only_live_pages():
    """The paged grid's context stream is the prefix-counted page list:
    exactly sum(ceil(len/page_m)) blocks — FREE segments and dead capacity
    contribute ZERO entries, and the padded tail repeats the last live
    page (same block index => the revisiting rule elides its DMA). The
    dense tree grid streams n_nodes * (capacity/block) blocks regardless."""
    case = make_paged_trie(**RAGGED)
    ids, segs, n_live, bias = live_page_list(case["tables"], case["nlens"],
                                             case["page_m"])
    expect = sum(pages_needed(m, case["page_m"]) for m in RAGGED["node_lens"])
    assert int(n_live[0]) == expect
    # context blocks streamed = distinct consecutive block indices
    ids_np = np.asarray(ids)
    streamed = 1 + int(np.sum(ids_np[1:] != ids_np[:-1]))
    assert streamed == expect
    # dense envelope for the same trie: every node, every capacity block
    dense_blocks = len(RAGGED["node_lens"]) * (case["cap"] // case["page_m"])
    assert streamed < dense_blocks
    # (segment, page) stream order — the dense kernels' (node, block) order
    np.testing.assert_array_equal(np.asarray(segs)[:expect],
                                  [0, 0, 0, 1, 2, 2])
    # clearing a segment structurally shrinks the stream
    tables2 = case["tables"].at[0].set(-1)
    nlens2 = case["nlens"].at[0].set(0)
    _, _, n_live2, _ = live_page_list(tables2, nlens2, case["page_m"])
    assert int(n_live2[0]) == expect - pages_needed(160, case["page_m"])


def test_live_page_list_bias_masks_ragged_tails():
    case = make_paged_trie(**RAGGED)
    ids, segs, n_live, bias = live_page_list(case["tables"], case["nlens"],
                                             case["page_m"])
    bias = np.asarray(bias)
    # node 1 (len 37) occupies one 64-token page: cols 37.. masked
    entry = int(np.where(np.asarray(segs)[:int(n_live[0])] == 1)[0][0])
    assert (bias[entry, :37] == 0).all() and (bias[entry, 37:] < -1e29).all()


# ---------------------------------------------------------------------------
# Kernel exactness: bit-identical to the dense tree kernels
# ---------------------------------------------------------------------------

def test_paged_kernel_bit_identical_to_dense_tree():
    """ISSUE acceptance: on the same logical contents (ragged lengths,
    permuted pool pages, a FREE node) the paged kernel's output is
    BIT-identical to the dense tree kernel at block_m == page_m — the
    skipped blocks' contributions are exact zeros (or pre-first-column
    state wiped by the corr == 0 rescale), both dtypes."""
    case = make_paged_trie(**RAGGED)
    out_d = tree_bifurcated_decode_attention(
        case["q"], case["kc"], case["vc"], case["paths"], case["nlens"],
        case["kd"], case["vd"], case["mask"],
        block_m=case["page_m"], interpret=True, ctx_layout="gmk")
    out_p = paged_bifurcated_decode_attention(
        case["q"], case["kp"], case["vp"], case["tables"], case["nlens"],
        case["paths"], case["kd"], case["vd"], case["mask"], interpret=True)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_d))

    out_dq = tree_bifurcated_decode_attention_q8(
        case["q"], case["kq"], case["vq"], case["ks"], case["vs"],
        case["paths"], case["nlens"], case["kd"], case["vd"], case["mask"],
        block_m=case["page_m"], interpret=True, ctx_layout="gmk")
    out_pq = paged_bifurcated_decode_attention_q8(
        case["q"], case["kpq"], case["vpq"], case["ksp"], case["vsp"],
        case["tables"], case["nlens"], case["paths"],
        case["kd"], case["vd"], case["mask"], interpret=True)
    np.testing.assert_array_equal(np.asarray(out_pq), np.asarray(out_dq))


def test_paged_kernel_vs_concat_oracle():
    """Multi-level correctness in f32: each slot's paged output equals
    standard attention over [its path's concatenated live context ⊕ its
    decode slots]."""
    from repro.core.attention import decode_attention

    case = make_paged_trie(**RAGGED, dtype=jnp.float32, seed=3)
    out = paged_bifurcated_decode_attention(
        case["q"], case["kp"], case["vp"], case["tables"], case["nlens"],
        case["paths"], case["kd"], case["vd"], case["mask"], interpret=True)
    paths = np.asarray(case["paths"])
    lens = np.asarray(case["nlens"])
    kc = np.asarray(case["kc"], np.float32)   # (N, g, cap, hd)
    vc = np.asarray(case["vc"], np.float32)
    for s in range(paths.shape[1]):
        pth = [int(n) for n in paths[:, s] if n >= 0]
        ks = np.concatenate([kc[n, :, :lens[n]] for n in pth], axis=1)
        vs = np.concatenate([vc[n, :, :lens[n]] for n in pth], axis=1)
        m = ks.shape[1]
        K = jnp.asarray(ks.transpose(1, 0, 2))[None]   # (1, m, g, hd)
        V = jnp.asarray(vs.transpose(1, 0, 2))[None]
        K = jnp.concatenate([K, case["kd"][s:s + 1]], axis=1)
        V = jnp.concatenate([V, case["vd"][s:s + 1]], axis=1)
        valid = jnp.concatenate(
            [jnp.ones((1, m), bool), case["mask"][s:s + 1]], axis=1)
        ref = decode_attention(case["q"][s:s + 1], K, V, valid_mask=valid)
        np.testing.assert_allclose(np.asarray(out[s:s + 1]),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_paged_kernel_no_hbm_spill_and_no_dequant():
    """The fused structural contract holds for the paged kernels: ONE
    pallas_call whose only output is the normalized result, and (q8) the
    pool enters exclusively as int8 — no dequantized page buffer in HBM."""
    case = make_paged_trie(**RAGGED)
    jaxpr = jax.make_jaxpr(
        lambda *a: paged_bifurcated_decode_attention(*a, interpret=True))(
        case["q"], case["kp"], case["vp"], case["tables"], case["nlens"],
        case["paths"], case["kd"], case["vd"], case["mask"])
    assert_no_hbm_spill(jaxpr.jaxpr, out_dtype=jnp.bfloat16)
    jaxpr_q8 = jax.make_jaxpr(
        lambda *a: paged_bifurcated_decode_attention_q8(*a, interpret=True))(
        case["q"], case["kpq"], case["vpq"], case["ksp"], case["vsp"],
        case["tables"], case["nlens"], case["paths"],
        case["kd"], case["vd"], case["mask"])
    assert_no_hbm_spill(jaxpr_q8.jaxpr, out_dtype=jnp.bfloat16, hd=HD,
                        q8=True)


# ---------------------------------------------------------------------------
# Paged cache families
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", ["none", "int8"])
@pytest.mark.parametrize("fam,args", [
    (PagedPrefixTreeCache, (2, 3, 2, 4, 96, 8, G, HD)),
    (PagedGroupedBifurcatedCache, (2, 3, 4, 96, 8, G, HD)),
])
def test_paged_cache_spec_matches_init(fam, args, quant):
    spec = fam.spec(*args, page_m=32, ctx_quant=quant)
    real = fam.init(*args, page_m=32, ctx_quant=quant)
    assert jax.tree.structure(spec) == jax.tree.structure(real)
    for s, r in zip(jax.tree.leaves(spec), jax.tree.leaves(real)):
        assert s.shape == r.shape and s.dtype == r.dtype
    assert spec.decode_capacity == 8 and spec.page_m == 32
    store = spec.store
    assert store.segment_capacity == 96 and store.pages_per_segment == 3
    assert store.num_pages == 9          # full envelope by default


def test_paged_cache_oversubscribed_pool():
    c = PagedPrefixTreeCache.init(1, 8, 2, 4, 256, 8, G, HD,
                                  page_m=64, num_pages=12)
    assert c.store.num_pages == 12       # < 8 * 4 = 32 table envelope
    assert c.node_capacity == 256


def test_paged_assign_paths_wipes_stale_decode_arm():
    c = PagedPrefixTreeCache.init(1, 4, 2, 4, 16, 8, G, HD, page_m=8)
    c = dataclasses.replace(
        c, k_dec=jnp.ones_like(c.k_dec),
        dec_lens=jnp.full((4,), 5, jnp.int32),
        paths=jnp.asarray([[0, 0, 1, 1], [2, -1, 3, -1]], jnp.int32))
    mask = jnp.asarray([False, True, True, False])
    c = c.assign_paths(mask, jnp.asarray([1, 3], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(c.paths), [[0, 1, 1, 1], [2, 3, 3, -1]])
    np.testing.assert_array_equal(np.asarray(c.dec_lens), [5, 0, 0, 5])
    assert float(jnp.max(jnp.abs(c.k_dec[:, 1]))) == 0
    assert float(jnp.min(jnp.abs(c.k_dec[:, 0]))) == 1


def test_single_prefix_cache_adapter_views():
    rng = np.random.RandomState(2)
    k = jnp.asarray(rng.randn(1, 21, G, HD), jnp.bfloat16)
    c = PagedBifurcatedCache.from_prefill(k, k, 3, 8, page_m=8)
    assert int(c.context_len) == 21
    assert c.store.num_pages == 3        # exactly ceil(21/8)
    np.testing.assert_array_equal(np.asarray(c.slot_paths()), [[0, 0, 0]])
    np.testing.assert_array_equal(np.asarray(c.slot_context_lens()),
                                  [21, 21, 21])
    c = c.advance_decode(c.k_dec, c.v_dec, 2)
    np.testing.assert_array_equal(np.asarray(c.slot_dec_lens()), [2, 2, 2])


def test_gather_pages_matches_dense_layout():
    case = make_paged_trie(**RAGGED)
    kd = gather_pages(case["kp"], case["tables"])    # per-layer form
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(case["kc"]))


# ---------------------------------------------------------------------------
# IO model
# ---------------------------------------------------------------------------

def test_paged_decode_io_bytes_page_rounding_and_envelopes():
    from repro.core.io_model import paged_decode_io_bytes

    io = paged_decode_io_bytes(
        node_lens=[160, 37, 96, 0], page_m=64, c_d=8, g=G, hd=HD, b=4,
        node_capacity=192, n_nodes=4)
    per_tok = 2 * G * HD * 2
    assert io["per_node"][0] == 192 * per_tok     # 160 -> 3 pages
    assert io["per_node"][1] == 64 * per_tok      # 37 -> 1 page
    assert io["per_node"][3] == 0                 # FREE node: zero bytes
    fixed = io["total"] - sum(io["per_node"])
    assert io["live_total"] == (160 + 37 + 96) * per_tok + fixed
    assert io["dense_total"] == 4 * 192 * per_tok + fixed
    assert 1.0 <= io["paged_overhead_vs_live"] < 1.35
    assert io["saving_vs_dense"] > 1.5
    io_q8 = paged_decode_io_bytes(
        node_lens=[160, 37, 96, 0], page_m=64, c_d=8, g=G, hd=HD, b=4,
        impl="paged_q8", node_capacity=192, n_nodes=4)
    assert io_q8["total"] < io["total"]           # int8 pages cost less


# ---------------------------------------------------------------------------
# Model-level decode + sharding (slow tier)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    from repro.configs import get_config, reduced_config
    from repro.models import get_model

    cfg = reduced_config(get_config("internlm2-1.8b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.mark.slow
@pytest.mark.parametrize("quant", ["none", "int8"])
def test_paged_decode_step_kernel_matches_einsum(small_model, quant):
    """Model-level dispatch: the paged kernel path and the dense-
    materializing einsum escape hatch agree on a ragged paged trie."""
    cfg, model, params = small_model
    c = PagedPrefixTreeCache.init(
        cfg.n_layers, 3, 2, 4, 32, 8, cfg.n_kv_heads_padded, cfg.kq_dim,
        page_m=8, ctx_quant=quant)
    rng = np.random.RandomState(0)
    kv = lambda m: (jnp.asarray(
        rng.randn(cfg.n_layers, m, cfg.n_kv_heads_padded, cfg.kq_dim),
        jnp.bfloat16),) * 2
    c = c.write_node(*kv(21), 0, [0, 1, 2])
    c = c.write_node(*kv(9), 2, [5, 3])
    c = c.assign_paths(jnp.asarray([True, True, False, True]),
                       jnp.asarray([0, 2], jnp.int32))
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 1)))
    le, ce = model.decode_step(params, c, toks, None, impl="einsum")
    lk, ck = model.decode_step(params, c, toks, None, impl="kernel")
    le, lk = np.asarray(le, np.float32), np.asarray(lk, np.float32)
    scale = max(float(np.max(np.abs(le))), 1.0)
    assert float(np.max(np.abs(le - lk))) <= 2e-2 * scale
    np.testing.assert_array_equal(np.asarray(ce.dec_lens),
                                  np.asarray(ck.dec_lens))


def test_paged_cache_pspec_pool_head_axis(small_model):
    """launch.steps.cache_pspec_tree shards the page pool's HEAD axis over
    "model" (dim 2 of (L, P, g, pm, hd) — the sequence axis is
    page-chunked), scale pages following identically, with page tables /
    lengths / paths replicated."""
    from repro.launch import specs as S, steps as ST

    cfg, model, _ = small_model
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    rep = jax.sharding.PartitionSpec()
    for quant in ("none", "int8"):
        io = S.paged_decode_cache_specs(
            cfg, model, slots=4, n_segments=2, depth=2, node_capacity=64,
            page_m=32, dec_capacity=8, ctx_quant=quant)
        ps = ST.cache_pspec_tree(mesh, io["cache"])
        assert ps.store.k_pages[2] == "model"     # pool head axis sharded
        assert all(ax is None for i, ax in enumerate(ps.store.k_pages)
                   if i != 2)
        assert ps.k_dec[2] == "model"
        if quant == "int8":
            assert ps.store.k_scale_pages[2] == "model"  # scales follow
        assert ps.store.page_tables == rep
        assert ps.store.seg_lens == rep
        assert ps.paths == rep and ps.dec_lens == rep


@pytest.mark.slow
def test_paged_decode_spmd_compiles_on_8_devices():
    """Paged decode_step lowers + compiles under an 8-device (2, 4) SPMD
    mesh with the paged cache sharded by launch.steps.cache_pspec_tree
    (pool head axis over "model"), bf16 AND int8 stores — and the int8
    pool shrinks the argument bytes."""
    import json
    import os
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    code = """
        import json, jax, jax.numpy as jnp
        from repro.configs import get_config, reduced_config
        from repro.launch import specs as S, steps as ST
        from repro.models import get_model

        cfg = reduced_config(get_config("internlm2-1.8b"))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        out = {}
        with mesh:
            model = get_model(cfg)
            params = S.param_specs(model)
            rules = ST.MeshRules.serving()
            psh = ST.to_named(mesh, ST.param_pspec_tree(params, rules))
            for quant in ("none", "int8"):
                io = S.paged_decode_cache_specs(
                    cfg, model, slots=4, n_segments=2, depth=2,
                    node_capacity=64, page_m=32, dec_capacity=8,
                    ctx_quant=quant)
                csh = ST.to_named(mesh, ST.cache_pspec_tree(mesh, io["cache"]))
                tsh = ST.to_named(mesh, ST.batch_pspec_tree(
                    mesh, {"tokens": io["tokens"]}))["tokens"]
                compiled = jax.jit(
                    lambda p, c, t: model.decode_step(p, c, t, None),
                    in_shardings=(psh, csh, tsh), donate_argnums=(1,),
                ).lower(params, io["cache"], io["tokens"]).compile()
                out[quant] = int(
                    compiled.memory_analysis().argument_size_in_bytes)
        print(json.dumps(out))
    """
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=560, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["none"] > 0 and out["int8"] > 0
    assert out["int8"] < out["none"]     # int8 pool shrinks the args


# ---------------------------------------------------------------------------
# Engines under ctx_store="paged" (slow tier)
# ---------------------------------------------------------------------------

def _engines(small_model):
    from repro.configs import ForestConfig, TreeConfig
    from repro.runtime.serve import ForestServeEngine, TreeServeEngine

    cfg, model, params = small_model

    def forest(ctx_store="dense", **kw):
        base = dict(n_groups=2, slots=5, ctx_capacity=32, decode_capacity=16,
                    temperature=0.0, ctx_store=ctx_store, page_size=8)
        base.update(kw)
        return ForestServeEngine(model, cfg, ForestConfig(**base))

    def tree(ctx_store="dense", **kw):
        base = dict(n_nodes=4, depth=2, slots=5, node_capacity=32,
                    decode_capacity=16, temperature=0.0,
                    ctx_store=ctx_store, page_size=8)
        base.update(kw)
        return TreeServeEngine(model, cfg, TreeConfig(**base))

    return forest, tree, params


@pytest.fixture(scope="module")
def req_tokens(small_model):
    cfg = small_model[0]
    rng = np.random.RandomState(0)
    return {
        "sys": jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 12))),
        "a": jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 9))),
        "b": jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 7))),
    }


@pytest.mark.slow
@pytest.mark.parametrize("cache_dtype,use_kernel", [
    ("bfloat16", True), ("int8", True), ("bfloat16", False),
])
def test_forest_engine_paged_matches_dense(small_model, req_tokens,
                                           cache_dtype, use_kernel):
    """ISSUE acceptance: ctx_store="paged" serves the exact dense-forest
    workload — greedy tokens identical across admit/decode, kernel and
    einsum paths, bf16 and int8 pools."""
    forest, _, params = _engines(small_model)
    outs = {}
    for store in ("dense", "paged"):
        eng = forest(store, cache_dtype=cache_dtype, use_kernel=use_kernel)
        st = eng.init_state()
        st, _ = eng.admit(params, st, req_tokens["a"], 3)
        st, _ = eng.admit(params, st, req_tokens["b"], 2)
        st = eng.step_chunk(params, st, 6)
        outs[store] = [eng.outputs[s] for s in range(5)]
    assert outs["dense"] == outs["paged"]


@pytest.mark.slow
def test_tree_engine_paged_reuse_refcounts_and_release(small_model,
                                                       req_tokens):
    """Paged trie serving end-to-end: greedy tokens match the dense tree
    engine; reused ancestors allocate NO new pages; retirement returns
    leaf pages to the allocator while the shared root's pages survive;
    release_retired structurally shrinks the live-page stream; decode
    compiles ONCE across admit/step/retire/readmit."""
    _, tree, params = _engines(small_model)
    d = tree("dense")
    ds = d.init_state()
    ds, _ = d.admit(params, ds, [req_tokens["sys"], req_tokens["a"]], 2)
    ds = d.step_chunk(params, ds, 4)

    p = tree("paged")
    ps = p.init_state()
    ps, slots_a = p.admit(params, ps, [req_tokens["sys"], req_tokens["a"]], 2)
    ps = p.step_chunk(params, ps, 4)
    assert [p.outputs[s] for s in slots_a] == \
        [d.outputs[s] for s in range(2)]

    used_after_a = p.num_pages - p.page_alloc.free_count()
    # second request shares [sys]: only the new leaf allocates pages
    ps, slots_b = p.admit(params, ps, [req_tokens["sys"], req_tokens["b"]], 2)
    leaf_pages = (p.num_pages - p.page_alloc.free_count()) - used_after_a
    assert leaf_pages == pages_needed(int(req_tokens["b"].shape[1]),
                                      p.tcfg.page_size)
    ps = p.step_chunk(params, ps, 4)

    # force-retire request A: its leaf's pages free, the shared root's stay
    ps = dataclasses.replace(
        ps, active=ps.active & ~jnp.isin(jnp.arange(5),
                                         jnp.asarray(slots_a)))
    free_before = p.page_alloc.free_count()
    assert p.retire_requests(ps) == [0]
    a_leaf_pages = pages_needed(int(req_tokens["a"].shape[1]),
                                p.tcfg.page_size)
    assert p.page_alloc.free_count() == free_before + a_leaf_pages
    assert p.node_live[0]                       # root survives (refcounted)

    # release_retired: freed node's pages leave the decode stream
    from repro.kernels.ops import live_page_list

    before = int(live_page_list(ps.cache.store.page_tables,
                                ps.cache.store.seg_lens,
                                p.tcfg.page_size)[2][0])
    ps = p.release_retired(ps)
    after = int(live_page_list(ps.cache.store.page_tables,
                               ps.cache.store.seg_lens,
                               p.tcfg.page_size)[2][0])
    assert after == before - a_leaf_pages

    # readmit A: node + pages recycle, decode never recompiles
    ps, slots_c = p.admit(params, ps, [req_tokens["sys"], req_tokens["a"]], 2)
    ps = p.step_chunk(params, ps, 4)
    assert p._chunk._cache_size() == 1          # ONE compile throughout
    fresh = tree("paged")
    fs = fresh.init_state()
    fs, fslots = fresh.admit(params, fs,
                             [req_tokens["sys"], req_tokens["a"]], 2)
    fs = fresh.step_chunk(params, fs, 4)
    for s_new, s_fresh in zip(slots_c, fslots):
        assert p.outputs[s_new] == fresh.outputs[s_fresh]


@pytest.mark.slow
@pytest.mark.parametrize("cache_dtype,use_kernel", [
    ("bfloat16", True), ("int8", True), ("bfloat16", False),
])
def test_serve_engine_paged_matches_dense(small_model, cache_dtype,
                                          use_kernel):
    """The single-prefix ServeEngine under ctx_store="paged" (the
    serve.py prefill_shared -> PagedBifurcatedCache branch): greedy
    tokens identical to the dense engine through the jitted scan decode,
    kernel and einsum paths, bf16 and int8 pools. The BifurcationPolicy
    gate still applies, so the context must be large enough to bifurcate
    — asserted so this test can't silently degrade to DecodeCache."""
    from repro.configs import ServeConfig
    from repro.core.paged import PagedBifurcatedCache
    from repro.runtime.serve import ServeEngine

    cfg, model, params = small_model
    # the reduced config needs ~(b=8, m_c=2048) to cross the policy's 1 MB
    # modelled-saving threshold (see BifurcationPolicy)
    ctx = jnp.asarray(np.random.RandomState(5).randint(
        0, cfg.vocab_size, (1, 2000)))
    outs = {}
    for store in ("dense", "paged"):
        eng = ServeEngine(model, cfg, ServeConfig(
            batch=8, decode_capacity=8, temperature=0.0,
            cache_dtype=cache_dtype, use_kernel=use_kernel,
            ctx_store=store, page_size=128))
        assert eng.should_bifurcate(8, int(ctx.shape[1]))
        _, cache = eng.prefill_shared(params, ctx, 8)
        if store == "paged":
            assert isinstance(cache, PagedBifurcatedCache)
            assert cache.store.num_pages == 16   # ceil(2000/128), exact fit
            assert int(cache.context_len) == 2000
        outs[store] = eng.generate(params, ctx, n_steps=5).tokens
    np.testing.assert_array_equal(np.asarray(outs["dense"]),
                                  np.asarray(outs["paged"]))


@pytest.mark.slow
def test_admit_clears_stale_tables_no_page_aliasing(small_model,
                                                    req_tokens):
    """Pages released at retire may be re-allocated by the very next
    admit; admit must clear the retired segments' stale table rows FIRST,
    so no pool page is ever referenced by two segments and the page walk
    never streams a page twice (n_live == the new segment's pages only)."""
    forest, _, params = _engines(small_model)
    eng = forest("paged", num_pages=2)
    st = eng.init_state()
    st, slots = eng.admit(params, st, req_tokens["a"], 2)   # 9 tok, 2 pages
    # force-retire group 0; its 2 pages return to the allocator but the
    # device table row still references them
    st = dataclasses.replace(st, active=jnp.zeros_like(st.active))
    assert eng.retire_groups(st) == [0]
    assert eng.page_alloc.free_count() == 2
    # next admit re-allocates those SAME pages into group 1
    st, _ = eng.admit(params, st, req_tokens["b"], 2)       # 7 tok, 1 page
    n_live = int(live_page_list(st.cache.store.page_tables,
                                st.cache.store.seg_lens,
                                eng.fcfg.page_size)[2][0])
    assert n_live == 1          # ONLY the new segment's page streams
    tables = np.asarray(st.cache.store.page_tables)
    live_ids = tables[tables >= 0]
    assert len(live_ids) == len(set(live_ids))   # no page owned twice


@pytest.mark.slow
def test_admission_rejection_capacity_and_pool(small_model, req_tokens):
    """Satellite: engines REJECT (clear errors) instead of silently
    truncating/overflowing — context > segment envelope (dense AND paged)
    and context > allocatable pool pages (paged oversubscription)."""
    cfg = small_model[0]
    forest, tree, params = _engines(small_model)
    long_ctx = jnp.zeros((1, 33), jnp.int32)    # > ctx_capacity = 32

    for store in ("dense", "paged"):
        eng = forest(store)
        st = eng.init_state()
        with pytest.raises(ValueError, match="exceeds the segment capacity"):
            eng.admit(params, st, long_ctx, 1)

    # oversubscribed pool: 2 segments' envelope but only 2 pages of 8
    eng = forest("paged", num_pages=2)
    st = eng.init_state()
    st, _ = eng.admit(params, st, req_tokens["a"], 2)   # 9 tok -> 2 pages
    with pytest.raises(RuntimeError, match="free — retire first"):
        eng.admit(params, st, req_tokens["b"], 1)

    teng = tree("paged", num_pages=2)
    ts = teng.init_state()
    with pytest.raises(RuntimeError, match="pool pages"):
        teng.admit(params, ts, [req_tokens["sys"], req_tokens["a"]], 1)
    # rejection happened BEFORE any state mutation: a fitting request lands
    ts, _ = teng.admit(params, ts, [req_tokens["b"]], 1)
    assert teng.node_live[0]


# ---------------------------------------------------------------------------
# Hardened allocator: atomic mutators + invariant auditing (PR 6)
# ---------------------------------------------------------------------------

def test_allocator_typed_errors_are_backward_compatible():
    """The new taxonomy subclasses the historical bare types, so existing
    ``except RuntimeError`` / ``except ValueError`` sites keep working."""
    from repro.core.errors import (
        AllocatorCorruption,
        CapacityError,
        PoolExhausted,
        SegmentCapacityExceeded,
    )

    assert issubclass(PoolExhausted, RuntimeError)
    assert issubclass(PoolExhausted, CapacityError)
    assert PoolExhausted.retryable
    assert issubclass(SegmentCapacityExceeded, ValueError)
    assert not SegmentCapacityExceeded.retryable
    assert issubclass(AllocatorCorruption, RuntimeError)

    al = PageAllocator(2)
    al.alloc(2)
    with pytest.raises(PoolExhausted):
        al.alloc(1)
    st = PagedKVStore.init(1, 2, 2, 8, G, HD, page_m=8)
    k = jnp.ones((1, 17, G, HD), jnp.bfloat16)
    with pytest.raises(SegmentCapacityExceeded):
        st.write_segment(k, k, 0, [0, 1, 2])


def test_allocator_alloc_atomic_on_exhaustion():
    """A rejected alloc grabs NOTHING: free list and refcounts untouched."""
    from repro.core.errors import PoolExhausted

    al = PageAllocator(4)
    al.alloc(3)
    before = al.free_pages()
    with pytest.raises(PoolExhausted):
        al.alloc(2)
    assert al.free_pages() == before
    assert al.alloc(1) == before                 # the survivor still works
    with pytest.raises(ValueError):
        al.alloc(-1)


def test_allocator_double_release_refused_atomically():
    """Double release (across calls AND duplicated within one call) raises
    AllocatorCorruption BEFORE mutating — the historical bug silently
    pushed the page onto the free list twice, aliasing HBM."""
    from repro.core.errors import AllocatorCorruption

    al = PageAllocator(4)
    a = al.alloc(2)
    assert al.release([a[0]]) == [a[0]]
    before = (al.free_pages(), al.free_count())
    with pytest.raises(AllocatorCorruption, match="double release"):
        al.release([a[0]])                       # already free
    with pytest.raises(AllocatorCorruption, match="double release"):
        al.release([a[1], a[1]])                 # dup within one call
    assert (al.free_pages(), al.free_count()) == before
    al.audit()                                   # invariants intact


def test_allocator_release_and_share_validate_ids():
    """Unknown page ids and shares of free pages are refused atomically."""
    from repro.core.errors import AllocatorCorruption

    al = PageAllocator(4)
    a = al.alloc(2)
    for bad in (99, -1, "x"):
        with pytest.raises(AllocatorCorruption, match="unknown page"):
            al.release([bad])
        with pytest.raises(AllocatorCorruption, match="unknown page"):
            al.share([bad])
    free = al.free_pages()[0]
    with pytest.raises(AllocatorCorruption, match="share of free page"):
        al.share([free])
    # a failed share mid-list increments NOTHING
    with pytest.raises(AllocatorCorruption):
        al.share([a[0], free])
    assert al.release(a) == a                    # refcounts were untouched
    al.audit()


def test_allocator_accepts_numpy_page_ids():
    """Engine mirrors hand back np.int32/int64 ids — the allocator must
    treat them as the same page, not 'unknown'."""
    al = PageAllocator(4)
    a = al.alloc(2)
    al.share(np.asarray(a, np.int32))
    al.release(np.asarray(a, np.int64))
    assert al.release(list(np.asarray(a, np.int32))) == a
    assert al.free_count() == 4
    al.audit()


def test_allocator_audit_catches_planted_corruption():
    """audit() re-derives every invariant from scratch: free-list damage,
    refcount drift, aliased live rows, out-of-pool rows, and host-mirror
    multiset mismatches each raise AllocatorCorruption."""
    from repro.core.errors import AllocatorCorruption

    def fresh():
        al = PageAllocator(4)
        ids = al.alloc(2)
        return al, ids

    al, ids = fresh()
    assert al.audit(rows=[np.asarray([ids[0], -1]),
                          np.asarray([ids[1]])],
                    tracked=ids) is True

    al, ids = fresh()
    al._free.append(ids[0])                      # resurrect a held page
    with pytest.raises(AllocatorCorruption, match="free list"):
        al.audit()

    al, ids = fresh()
    al._refs[ids[0]] = -1                        # refcount drift
    with pytest.raises(AllocatorCorruption, match="negative refcount"):
        al.audit()

    al, ids = fresh()                            # two live rows, one page
    with pytest.raises(AllocatorCorruption, match="two live segments"):
        al.audit(rows=[np.asarray([ids[0]]), np.asarray([ids[0]])])

    al, ids = fresh()                            # row points outside pool
    with pytest.raises(AllocatorCorruption, match="outside the pool"):
        al.audit(rows=[np.asarray([7])])

    al, ids = fresh()                            # row references free page
    free = al.free_pages()[0]
    with pytest.raises(AllocatorCorruption, match="FREE"):
        al.audit(rows=[np.asarray([free])])

    al, ids = fresh()                            # mirror lost a page
    with pytest.raises(AllocatorCorruption, match="host mirrors"):
        al.audit(tracked=[ids[0]])
