"""Hierarchical prefix-trie (cascade) decoding + TreeServeEngine.

Covers the tentpole acceptance criteria beyond the bit-identity reductions
(which live in tests/test_differential.py):
  * tree caches (bf16 + int8): write_node admission, path assignment /
    slot reuse, per-slot context lengths, spec surfaces, both layouts;
  * multi-level correctness: the tree kernel AND the cascade einsum
    reference against a per-slot concatenated-context oracle on a real
    depth-2/3 trie with node reuse across paths and -1 (unused) levels;
  * structural no-HBM-spill for the tree kernels (bf16 + the q8 no-dequant
    guarantee) and tree sharding specs;
  * TreeServeEngine end-to-end: depth-1 admission serves the EXACT
    flat-forest workload (greedy tokens identical to ForestServeEngine,
    einsum and kernel paths), longest-matching-prefix node reuse, decode
    compiles once across admits, refcounted retirement;
  * per-node IO accounting (core.io_model.tree_decode_io_bytes): the L=3
    trie beats the flat-forest replay of the same traffic.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_no_hbm_spill, make_decode_case
from repro.configs import ForestConfig, TreeConfig, get_config, reduced_config
from repro.core.kv_cache import PrefixTreeCache
from repro.core.quantized import QuantPrefixTreeCache, quantize_ctx
from repro.models import get_model
from repro.runtime.serve import ForestServeEngine, TreeServeEngine

pytestmark = pytest.mark.slow  # CI runs the slow tier in its own step

G, HD = 2, 32

CFG = reduced_config(get_config("internlm2-1.8b"))
MODEL = get_model(CFG)
PARAMS = MODEL.init(jax.random.PRNGKey(0))
RNG = np.random.RandomState(0)
SYS = jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 12)))      # shared root
TPL = jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 6)))       # template
REQ_A = jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 9)))
REQ_B = jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 7)))


def _tree(n_nodes=4, depth=2, slots=5, cache_dtype="bfloat16",
          use_kernel=False, **kw):
    tcfg = TreeConfig(n_nodes=n_nodes, depth=depth, slots=slots,
                      node_capacity=32, decode_capacity=16, temperature=0.0,
                      cache_dtype=cache_dtype, use_kernel=use_kernel, **kw)
    return TreeServeEngine(MODEL, CFG, tcfg)


def _forest(n_groups=2, slots=5, cache_dtype="bfloat16", use_kernel=False,
            ctx_capacity=32, **kw):
    fcfg = ForestConfig(n_groups=n_groups, slots=slots,
                        ctx_capacity=ctx_capacity, decode_capacity=16,
                        temperature=0.0, cache_dtype=cache_dtype,
                        use_kernel=use_kernel, **kw)
    return ForestServeEngine(MODEL, CFG, fcfg)


# ---------------------------------------------------------------------------
# Tree caches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["gmk", "mgk"])
def test_tree_cache_write_node_and_lens(layout):
    cache = PrefixTreeCache.init(2, 3, 2, 4, 32, 8, 2, 16, ctx_layout=layout)
    k = jnp.ones((2, 20, 2, 16), jnp.float32)
    cache = cache.write_node(k, k * 2, 1)
    assert int(cache.node_lens[1]) == 20 and int(cache.node_lens[0]) == 0
    seg = cache.k_ctx[:, 1]
    live = seg[:, :, :20] if layout == "gmk" else seg[:, :20]
    dead = seg[:, :, 20:] if layout == "gmk" else seg[:, 20:]
    assert float(jnp.min(jnp.abs(live))) > 0          # node written
    assert float(jnp.max(jnp.abs(dead))) == 0         # capacity tail zero
    assert float(jnp.max(jnp.abs(cache.k_ctx[:, 0]))) == 0  # others intact


def test_tree_cache_assign_paths_wipes_stale_decode_arm():
    cache = PrefixTreeCache.init(1, 4, 2, 4, 16, 8, 2, 16)
    cache = dataclasses.replace(
        cache, k_dec=jnp.ones_like(cache.k_dec),
        dec_lens=jnp.full((4,), 5, jnp.int32),
        paths=jnp.asarray([[0, 0, 1, 1], [2, -1, 3, -1]], jnp.int32))
    mask = jnp.asarray([False, True, True, False])
    cache = cache.assign_paths(mask, jnp.asarray([1, 3], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(cache.paths), [[0, 1, 1, 1], [2, 3, 3, -1]])
    np.testing.assert_array_equal(np.asarray(cache.dec_lens), [5, 0, 0, 5])
    assert float(jnp.max(jnp.abs(cache.k_dec[:, 1]))) == 0   # wiped
    assert float(jnp.min(jnp.abs(cache.k_dec[:, 0]))) == 1   # kept


def test_tree_cache_slot_context_lens_sums_path():
    cache = PrefixTreeCache.init(1, 4, 3, 3, 32, 8, 2, 16)
    k20 = jnp.ones((1, 20, 2, 16), jnp.float32)
    k7 = jnp.ones((1, 7, 2, 16), jnp.float32)
    cache = cache.write_node(k20, k20, 0).write_node(k7, k7, 2)
    cache = dataclasses.replace(
        cache, paths=jnp.asarray(
            [[0, 0, -1], [2, -1, -1], [-1, -1, -1]], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(cache.slot_context_lens()), [27, 20, 0])


@pytest.mark.parametrize("fam", [PrefixTreeCache, QuantPrefixTreeCache])
def test_tree_cache_spec_matches_init(fam):
    spec = fam.spec(2, 3, 2, 4, 32, 8, 2, 16)
    real = fam.init(2, 3, 2, 4, 32, 8, 2, 16)
    assert jax.tree.structure(spec) == jax.tree.structure(real)
    for s, r in zip(jax.tree.leaves(spec), jax.tree.leaves(real)):
        assert s.shape == r.shape and s.dtype == r.dtype
    assert spec.n_nodes == 3 and spec.depth == 2
    assert spec.node_capacity == 32 and spec.n_slots == 4
    assert spec.decode_capacity == 8


def test_tree_quant_cache_quantizes_at_admission():
    cache = QuantPrefixTreeCache.init(2, 2, 2, 4, 32, 8, 2, 16)
    rng = np.random.RandomState(3)
    k = jnp.asarray(rng.randn(2, 20, 2, 16), jnp.float32)
    cache = cache.write_node(k, k, 0)
    assert cache.k_ctx.dtype == jnp.int8
    assert int(cache.node_lens[0]) == 20
    # k scales carry the logit fold: smaller than the raw v scales
    ks = np.asarray(cache.k_scale[:, 0, :, :20])
    vs = np.asarray(cache.v_scale[:, 0, :, :20])
    assert ks.min() > 0 and np.all(ks < vs)
    np.testing.assert_allclose(ks * 16**0.5, vs, rtol=1e-5)


# ---------------------------------------------------------------------------
# Multi-level correctness vs the concatenated-context oracle
# ---------------------------------------------------------------------------

def _trie_case(dtype=jnp.float32, seed=7):
    """A real depth-2 trie over 4 nodes with node reuse and one depth-1
    slot: node 0 = shared root, nodes 1/2 = leaves, node 3 = a standalone
    single-level prefix."""
    rng = np.random.RandomState(seed)
    b, p, n, c_d = 5, 2, 1, 8
    n_nodes, cap = 4, 96
    case = {
        "q": jnp.asarray(rng.randn(b, G, p, n, HD), dtype),
        "kc": jnp.asarray(rng.randn(n_nodes, G, cap, HD), dtype),  # gmk
        "vc": jnp.asarray(rng.randn(n_nodes, G, cap, HD), dtype),
        "kd": jnp.asarray(rng.randn(b, c_d, G, HD), dtype),
        "vd": jnp.asarray(rng.randn(b, c_d, G, HD), dtype),
        "mask": jnp.arange(c_d)[None, :] < jnp.asarray(
            rng.randint(1, c_d + 1, size=(b,)))[:, None],
        "node_lens": jnp.asarray([64, 96, 37, 50], jnp.int32),
        # slots 0/3 share path (0,1); slot 2 shares the root via (0,2);
        # slot 4 is a depth-1 path on node 3 (level 1 unused: -1)
        "paths": jnp.asarray([[0, 0, 0, 0, 3],
                              [1, 2, 2, 1, -1]], jnp.int32),
    }
    return case


def _oracle_per_slot(case, out, rtol=1e-5, atol=1e-5):
    """Check ``out`` slot-by-slot against the single-prefix fused kernel on
    the CONCATENATION of the slot's path nodes."""
    from repro.kernels.ops import bifurcated_decode_attention

    paths = np.asarray(case["paths"])
    lens = np.asarray(case["node_lens"])
    for s in range(out.shape[0]):
        ks, vs = [], []
        for lvl in range(paths.shape[0]):
            nid = paths[lvl, s]
            if nid < 0:
                continue
            ks.append(case["kc"][nid, :, :lens[nid]])
            vs.append(case["vc"][nid, :, :lens[nid]])
        ref = bifurcated_decode_attention(
            case["q"][s:s + 1], jnp.concatenate(ks, axis=1),
            jnp.concatenate(vs, axis=1), case["kd"][s:s + 1],
            case["vd"][s:s + 1], case["mask"][s:s + 1],
            block_m=64, interpret=True, ctx_layout="gmk")
        np.testing.assert_allclose(np.asarray(out[s:s + 1]),
                                   np.asarray(ref), rtol=rtol, atol=atol)


def test_tree_kernel_multi_level_vs_concat_oracle():
    from repro.kernels.ops import tree_bifurcated_decode_attention

    case = _trie_case()
    out = tree_bifurcated_decode_attention(
        case["q"], case["kc"], case["vc"], case["paths"], case["node_lens"],
        case["kd"], case["vd"], case["mask"],
        block_m=64, interpret=True, ctx_layout="gmk")
    _oracle_per_slot(case, out)


def test_tree_einsum_multi_level_vs_concat_oracle():
    from repro.core.bifurcated import tree_bifurcated_attention

    case = _trie_case()
    out = tree_bifurcated_attention(
        case["q"], case["kc"], case["vc"], case["paths"], case["node_lens"],
        case["kd"], case["vd"], decode_mask=case["mask"], ctx_layout="gmk")
    _oracle_per_slot(case, out)


def test_tree_duplicate_node_in_path_set_semantics():
    """A node id repeated at several levels of one path contributes ONCE
    (set semantics): the kernel's OR-membership dedupes by construction
    and the einsum references mask duplicated levels to match — both must
    equal the single-occurrence path exactly."""
    from repro.core.bifurcated import tree_bifurcated_attention
    from repro.kernels.ops import tree_bifurcated_decode_attention

    case = _trie_case()
    dup = jnp.asarray([[0, 0, 0, 0, 3], [0, 0, 0, 0, 3]], jnp.int32)
    single = jnp.asarray([[0, 0, 0, 0, 3], [-1, -1, -1, -1, -1]], jnp.int32)
    args = (case["kc"], case["vc"])
    out_dup_k = tree_bifurcated_decode_attention(
        case["q"], *args, dup, case["node_lens"], case["kd"], case["vd"],
        case["mask"], block_m=64, interpret=True, ctx_layout="gmk")
    out_one_k = tree_bifurcated_decode_attention(
        case["q"], *args, single, case["node_lens"], case["kd"], case["vd"],
        case["mask"], block_m=64, interpret=True, ctx_layout="gmk")
    np.testing.assert_array_equal(np.asarray(out_dup_k),
                                  np.asarray(out_one_k))
    out_dup_e = tree_bifurcated_attention(
        case["q"], *args, dup, case["node_lens"], case["kd"], case["vd"],
        decode_mask=case["mask"], ctx_layout="gmk")
    out_one_e = tree_bifurcated_attention(
        case["q"], *args, single, case["node_lens"], case["kd"], case["vd"],
        decode_mask=case["mask"], ctx_layout="gmk")
    np.testing.assert_array_equal(np.asarray(out_dup_e),
                                  np.asarray(out_one_e))
    np.testing.assert_allclose(np.asarray(out_dup_k), np.asarray(out_dup_e),
                               rtol=1e-5, atol=1e-5)


def test_tree_q8_multi_level_kernel_vs_einsum():
    """Same scale-folded math, different execution order: the q8 kernel and
    the q8 cascade einsum reference agree at fp32 tightness on f32 inputs;
    both stay within int8 rounding of the unquantized kernel."""
    from repro.core.quantized import tree_bifurcated_attention_q8
    from repro.kernels.ops import (
        tree_bifurcated_decode_attention,
        tree_bifurcated_decode_attention_q8,
    )

    case = _trie_case()
    kq, ks = quantize_ctx(case["kc"], fold_scale=HD**-0.5)
    vq, vs = quantize_ctx(case["vc"])
    out_k = tree_bifurcated_decode_attention_q8(
        case["q"], kq, vq, ks, vs, case["paths"], case["node_lens"],
        case["kd"], case["vd"], case["mask"],
        block_m=64, interpret=True, ctx_layout="gmk")
    out_e = tree_bifurcated_attention_q8(
        case["q"], kq, vq, ks, vs, case["paths"], case["node_lens"],
        case["kd"], case["vd"], decode_mask=case["mask"], ctx_layout="gmk")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_e),
                               rtol=1e-5, atol=1e-5)
    out_fp = tree_bifurcated_decode_attention(
        case["q"], case["kc"], case["vc"], case["paths"], case["node_lens"],
        case["kd"], case["vd"], case["mask"],
        block_m=64, interpret=True, ctx_layout="gmk")
    scale = max(float(np.max(np.abs(np.asarray(out_fp)))), 1.0)
    assert float(np.max(np.abs(np.asarray(out_k) - np.asarray(out_fp)))) \
        <= 3e-2 * scale


# ---------------------------------------------------------------------------
# Structural + sharding
# ---------------------------------------------------------------------------

def test_tree_bf16_kernel_no_hbm_spill():
    """The tree (cascade) bf16 kernel keeps the fused-kernel guarantee:
    ONE pallas_call, one normalized bf16 output, no fp32 partials."""
    from repro.kernels.ops import tree_bifurcated_decode_attention

    case = make_decode_case(2, 2, 64, 8, g=2, hd=32, dtype=jnp.bfloat16,
                            seed=1, full_mask=True)
    paths = jnp.zeros((2, 2), jnp.int32)   # depth-2 table, both levels node 0
    clens = jnp.asarray([64], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: tree_bifurcated_decode_attention(
            *a, interpret=True, ctx_layout="mgk")
    )(case["q"], case["kc"][None], case["vc"][None], paths, clens,
      case["kd"], case["vd"], case["mask"]).jaxpr
    assert_no_hbm_spill(jaxpr, out_dtype=jnp.bfloat16)


def test_tree_q8_kernel_no_dequant_in_hbm():
    """The q8 tree kernel keeps the no-dequant guarantee: node K/V enter
    the pallas_call exclusively as int8; only q + the bf16 decode arm
    carry a head_dim axis as float operands."""
    from repro.kernels.ops import tree_bifurcated_decode_attention_q8

    case = make_decode_case(2, 2, 70, 8, g=2, hd=32, dtype=jnp.bfloat16,
                            seed=2, full_mask=True)
    kq, ks = quantize_ctx(case["kc"], fold_scale=HD**-0.5)
    vq, vs = quantize_ctx(case["vc"])
    paths = jnp.zeros((1, 2), jnp.int32)
    clens = jnp.asarray([70], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: tree_bifurcated_decode_attention_q8(
            *a, interpret=True, ctx_layout="mgk")
    )(case["q"], kq[None], vq[None], ks[None], vs[None], paths, clens,
      case["kd"], case["vd"], case["mask"]).jaxpr
    assert_no_hbm_spill(jaxpr, out_dtype=jnp.bfloat16, hd=32, q8=True)


@pytest.mark.parametrize("ctx_quant", ["none", "int8"])
@pytest.mark.parametrize("layout", ["gmk", "mgk"])
def test_tree_cache_pspec_tree_layout_aware(ctx_quant, layout):
    from repro.core.quantized import tree_cache_family
    from repro.launch.steps import cache_pspec_tree

    fam = tree_cache_family(ctx_quant)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    spec = fam.spec(2, 3, 2, 4, 64, 8, 2, 16, ctx_layout=layout)
    ps = cache_pspec_tree(mesh, spec)
    ctx_dim = 3 if layout == "gmk" else 2
    assert ps.k_ctx[ctx_dim] == "model"          # node seq dim sharded
    assert all(ax is None for i, ax in enumerate(ps.k_ctx) if i != ctx_dim)
    assert ps.k_dec[2] == "model"
    if ctx_quant == "int8":
        assert ps.k_scale[ctx_dim] == "model"    # scales follow the values
    assert ps.node_lens == jax.sharding.PartitionSpec()
    assert ps.paths == jax.sharding.PartitionSpec()


def test_tree_decode_cache_specs_build_and_decode():
    """launch.specs.tree_decode_cache_specs round-trips through an actual
    jitted decode_step (einsum path) without recompiling per admit."""
    from repro.launch import specs as S

    io = S.tree_decode_cache_specs(CFG, MODEL, slots=3, n_nodes=2, depth=2,
                                   node_capacity=32, dec_capacity=8)
    assert io["cache"].n_nodes == 2 and io["cache"].depth == 2
    assert io["tokens"].shape == (3, 1)
    # abstract spec lowers: eval_shape the decode step
    out = jax.eval_shape(
        lambda p, c, t: MODEL.decode_step(p, c, t, None),
        jax.eval_shape(MODEL.init, jax.random.PRNGKey(0)),
        io["cache"], io["tokens"])
    logits, cache2 = out
    assert logits.shape[0] == 3
    assert cache2.k_dec.shape == io["cache"].k_dec.shape


# ---------------------------------------------------------------------------
# TreeServeEngine end-to-end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache_dtype,use_kernel", [
    ("bfloat16", False), ("bfloat16", True),
    ("int8", False), ("int8", True),
])
def test_tree_engine_depth1_matches_forest(cache_dtype, use_kernel):
    """ISSUE acceptance: with every request a single segment (depth-1
    paths) the tree engine serves the EXACT flat-forest workload — greedy
    tokens identical to ForestServeEngine, bf16 and int8, einsum and
    kernel decode paths."""
    teng = _tree(n_nodes=2, depth=1, cache_dtype=cache_dtype,
                 use_kernel=use_kernel)
    ts = teng.init_state()
    ts, tsl_a = teng.admit(PARAMS, ts, [REQ_A], 3)
    ts, tsl_b = teng.admit(PARAMS, ts, [REQ_B], 2)
    ts = teng.step_chunk(PARAMS, ts, 7)

    feng = _forest(cache_dtype=cache_dtype, use_kernel=use_kernel)
    fs = feng.init_state()
    fs, fsl_a = feng.admit(PARAMS, fs, REQ_A, 3)
    fs, fsl_b = feng.admit(PARAMS, fs, REQ_B, 2)
    fs = feng.step_chunk(PARAMS, fs, 7)
    for t, f in zip(tsl_a + tsl_b, fsl_a + fsl_b):
        assert teng.outputs[t] == feng.outputs[f]
        np.testing.assert_allclose(teng.logps[t], feng.logps[f],
                                   rtol=1e-5, atol=1e-6)


def test_tree_engine_depth2_close_to_concat_forest():
    """A depth-2 trie request [SYS, REQ] must produce (numerically) the
    same next-token distribution as flat-forest serving of the
    concatenated prompt: the first sampled tokens agree exactly (same
    prefill) and the first decode step's logits agree to bf16 tolerance
    (the cascade merges two context levels where the flat path reads one
    concatenated segment — same math, different reduction order)."""
    teng = _tree(n_nodes=4, depth=2, slots=2)
    ts = teng.init_state()
    ts, slots = teng.admit(PARAMS, ts, [SYS, REQ_A], 2)

    feng = _forest(n_groups=1, slots=2, ctx_capacity=64)
    fs = feng.init_state()
    fs, fslots = feng.admit(PARAMS, fs, jnp.concatenate([SYS, REQ_A], 1), 2)
    # identical prefill => identical first tokens
    assert [teng.outputs[s][0] for s in slots] == \
        [feng.outputs[s][0] for s in fslots]
    lt, _ = MODEL.decode_step(PARAMS, ts.cache, ts.tokens, None)
    lf, _ = MODEL.decode_step(PARAMS, fs.cache, fs.tokens, None)
    lt = np.asarray(lt[:, -1], np.float32)
    lf = np.asarray(lf[:, -1], np.float32)
    scale = max(float(np.max(np.abs(lf))), 1.0)
    assert float(np.max(np.abs(lt - lf))) <= 2e-2 * scale
    np.testing.assert_array_equal(lt.argmax(-1), lf.argmax(-1))


def test_tree_engine_longest_prefix_reuse():
    """Admission matches the longest existing prefix path: a second
    request sharing [SYS] reuses the root node (no new segment, refcount
    bump), a third sharing [SYS, TPL] reuses two levels."""
    eng = _tree(n_nodes=6, depth=3, slots=6)
    st = eng.init_state()
    st, _ = eng.admit(PARAMS, st, [SYS, TPL, REQ_A], 2)
    assert eng.node_live.count(True) == 3
    assert eng.node_refs[:3] == [1, 1, 1]
    st, _ = eng.admit(PARAMS, st, [SYS, REQ_B], 2)       # reuse root only
    assert eng.node_live.count(True) == 4
    assert eng.node_refs[:4] == [2, 1, 1, 1]
    st, _ = eng.admit(PARAMS, st, [SYS, TPL, REQ_B], 2)  # reuse two levels
    assert eng.node_live.count(True) == 5
    assert eng.node_refs[:5] == [3, 2, 1, 1, 1]
    # reused root KV equals what a fresh write would produce: greedy
    # decode for the later admits is tested via logits in the depth-2 test;
    # here assert the device path table agrees with the host mirror
    paths = np.asarray(st.cache.paths)
    np.testing.assert_array_equal(paths[:, 0], [0, 1, 2])   # request 1
    np.testing.assert_array_equal(paths[:, 2], [0, 3, -1])  # request 2
    np.testing.assert_array_equal(paths[:, 4], [0, 1, 4])   # request 3


def test_tree_engine_compiles_once_across_admit_retire():
    """Trie admission state is data, not shape — the jitted decode chunk
    compiles exactly once across admit / step / retire / re-admit cycles,
    including node reuse and node recycling."""
    eng = _tree(n_nodes=4, depth=2, slots=4)
    st = eng.init_state()
    st, slots_a = eng.admit(PARAMS, st, [SYS, REQ_A], 2)
    st = eng.step_chunk(PARAMS, st, 4)
    st, slots_b = eng.admit(PARAMS, st, [SYS, REQ_B], 2)
    st = eng.step_chunk(PARAMS, st, 4)
    # force-retire request A; its leaf frees, the shared root survives
    st = dataclasses.replace(
        st, active=st.active & ~jnp.isin(jnp.arange(4),
                                         jnp.asarray(slots_a)))
    assert eng.retire_requests(st) == [0]
    assert eng.node_refs[0] == 1 and eng.node_live[0]    # root kept
    assert not eng.node_live[1]                          # leaf A freed
    st, slots_c = eng.admit(PARAMS, st, [SYS, REQ_A], 2)
    assert set(slots_c) == set(slots_a)                  # slots reused
    assert eng.node_live[1]                              # node recycled
    st = eng.step_chunk(PARAMS, st, 4)
    assert eng.decode_dispatches == 3
    assert eng._chunk._cache_size() == 1                 # ONE compile
    # readmitted request decodes like a fresh engine (stale arms wiped)
    fresh = _tree(n_nodes=4, depth=2, slots=4)
    fst = fresh.init_state()
    fst, fslots = fresh.admit(PARAMS, fst, [SYS, REQ_A], 2)
    fst = fresh.step_chunk(PARAMS, fst, 4)
    for s_new, s_fresh in zip(slots_c, fslots):
        assert eng.outputs[s_new] == fresh.outputs[s_fresh]


def test_tree_engine_retire_frees_shared_root_last():
    """Refcounted retirement: the shared root frees only when the LAST
    request referencing it retires, and its trie-index entry disappears
    with it (no stale matches against a recycled node id)."""
    eng = _tree(n_nodes=4, depth=2, slots=4)
    st = eng.init_state()
    st, sa = eng.admit(PARAMS, st, [SYS, REQ_A], 2)
    st, sb = eng.admit(PARAMS, st, [SYS, REQ_B], 2)
    st = dataclasses.replace(
        st, active=st.active & ~jnp.isin(jnp.arange(4), jnp.asarray(sa)))
    eng.retire_requests(st)
    assert eng.node_live[0]                      # root still referenced
    st = dataclasses.replace(st, active=jnp.zeros_like(st.active))
    eng.retire_requests(st)
    assert not any(eng.node_live)                # everything freed
    assert eng.node_index == {}                  # index emptied
    # freed slots + nodes admit again
    st, _ = eng.admit(PARAMS, st, [REQ_B], 1)
    assert eng.node_live.count(True) == 1


def test_tree_engine_admit_exhaustion_raises():
    eng = _tree(n_nodes=2, depth=2, slots=2)
    st = eng.init_state()
    st, _ = eng.admit(PARAMS, st, [SYS, REQ_A], 2)
    with pytest.raises(RuntimeError, match="free trie node"):
        eng.admit(PARAMS, st, [SYS, REQ_B], 0)   # root reused, leaf: none
    with pytest.raises(RuntimeError, match="free slots"):
        eng.admit(PARAMS, st, [SYS], 1)          # path reusable, no slots
    with pytest.raises(ValueError, match="levels"):
        eng.admit(PARAMS, st, [SYS, TPL, REQ_A], 1)   # deeper than depth
    with pytest.raises(ValueError, match="node capacity"):
        eng.admit(PARAMS, st, [jnp.zeros((1, 33), jnp.int32)], 1)


# ---------------------------------------------------------------------------
# Per-node IO accounting
# ---------------------------------------------------------------------------

def test_tree_io_bytes_per_node_accounting():
    from repro.core.io_model import (
        forest_decode_io_bytes,
        tree_decode_io_bytes,
    )

    # L=3 trie: shared root + 4 children, 16 slots round-robin
    paths = [(0, 1 + i % 4) for i in range(16)]
    io = tree_decode_io_bytes(paths=paths, node_lens=[2048] * 5, c_d=32,
                              g=8, hd=128)
    assert set(io["per_node"]) == {0, 1, 2, 3, 4}
    # ISSUE acceptance: the trie beats the flat-forest replay of the SAME
    # traffic — the root is read once, not once per distinct path
    assert io["total"] < io["forest_total"]
    assert io["io_saving_vs_forest"] > 1.4
    assert io["io_saving_vs_standard"] > io["io_saving_vs_forest"]
    # depth-1 single node reduces exactly to the G=1 forest (fused) model
    one = tree_decode_io_bytes(paths=[(0,)] * 16, node_lens=[4096], c_d=32,
                               g=8, hd=128)
    fo = forest_decode_io_bytes(group_sizes=[16], ctx_lens=[4096], c_d=32,
                                g=8, hd=128)
    assert one["total"] == fo["total"] == one["forest_total"]
    # flat (depth-1) tries coincide with their forest replay exactly
    flat = tree_decode_io_bytes(paths=[(i % 4,) for i in range(16)],
                                node_lens=[2048] * 4, c_d=32, g=8, hd=128)
    assert flat["total"] == flat["forest_total"]
    # q8 nodes halve the dominant (context) term; unreferenced nodes free
    q8 = tree_decode_io_bytes(paths=paths, node_lens=[2048] * 5, c_d=32,
                              g=8, hd=128, impl="tree_q8")
    assert q8["total"] < io["total"]
    # padded-envelope accounting costs more than live-length and coincides
    # when nodes are full
    env = tree_decode_io_bytes(paths=paths, node_lens=[1024] * 5, c_d=32,
                               g=8, hd=128, node_capacity=2048)
    live = tree_decode_io_bytes(paths=paths, node_lens=[1024] * 5, c_d=32,
                                g=8, hd=128)
    assert env["total"] > live["total"]
    full = tree_decode_io_bytes(paths=paths, node_lens=[2048] * 5, c_d=32,
                                g=8, hd=128, node_capacity=2048)
    assert full["total"] == io["total"]
    # the kernel's grid streams EVERY segment: n_nodes= accounts
    # unreferenced (free) segments in the envelope too
    sparse = tree_decode_io_bytes(paths=paths, node_lens=[2048] * 5,
                                  c_d=32, g=8, hd=128, node_capacity=2048,
                                  n_nodes=8)
    assert len(sparse["per_node"]) == 8
    assert sparse["total"] == full["total"] + 3 * 2 * 8 * 2048 * 128 * 2
