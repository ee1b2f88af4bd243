import os
import sys

# Tests run single-device (the 512-device override lives ONLY in
# launch/dryrun.py, per the brief).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

try:  # optional dep: CI installs it, local runs may not have it
    from hypothesis import settings as _hyp_settings

    # fixed-seed CI profile: deterministic example generation + no deadline
    # (interpret-mode Pallas kernels are slow on CPU); select with
    # HYPOTHESIS_PROFILE=ci in the workflow.
    _hyp_settings.register_profile("ci", max_examples=20, deadline=None,
                                   derandomize=True)
    _hyp_settings.register_profile("dev", max_examples=10, deadline=None)
    _hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))
except ImportError:
    pass


# ---------------------------------------------------------------------------
# Shared decode-case builder (differential harness + kernel test fixtures)
# ---------------------------------------------------------------------------

def make_decode_case(b, p, m_c, c_d, *, g=2, hd=32, n=1, dtype=jnp.float32,
                     seed=0, full_mask=False):
    """One bifurcated-decode problem in FRAMEWORK layouts, shared by every
    implementation under test:

      q:        (b, g, p, n, hd)
      kc, vc:   (m_c, g, hd)   — "mgk" shared context ("gmk" = transpose)
      kd, vd:   (b, c_d, g, hd)
      mask:     (b, c_d) bool  — ragged per-sample decode validity (sample 0
                always has >= 1 live slot; ``full_mask`` makes all live)

    Replaces the per-file ``make()`` copies in test_fused_decode /
    test_fused_q8 so every kernel/reference is exercised on IDENTICAL
    inputs (tests/test_differential.py cross-checks them pairwise).
    """
    rng = np.random.RandomState(seed)
    case = {
        "q": jnp.asarray(rng.randn(b, g, p, n, hd), dtype),
        "kc": jnp.asarray(rng.randn(m_c, g, hd), dtype),
        "vc": jnp.asarray(rng.randn(m_c, g, hd), dtype),
        "kd": jnp.asarray(rng.randn(b, c_d, g, hd), dtype),
        "vd": jnp.asarray(rng.randn(b, c_d, g, hd), dtype),
    }
    if full_mask:
        case["mask"] = jnp.ones((b, c_d), bool)
    else:
        lens = rng.randint(0, c_d + 1, size=(b,))
        lens[0] = max(1, lens[0])
        case["mask"] = jnp.arange(c_d)[None, :] < jnp.asarray(lens)[:, None]
    return case


# ---------------------------------------------------------------------------
# Shared page-pool builder (paged-kernel tests + differential harness)
# ---------------------------------------------------------------------------

def build_page_pool(arrays, node_lens, page_m, *, perm_seed=0,
                    extra_pages=0):
    """Split dense head-major per-segment slabs into a SHUFFLED page pool.

    ``arrays``: sequence of (N, g, cap[, hd]) slabs sharing the token axis
    at dim 2 (values, and for q8 the matching scale slabs); ``node_lens``:
    live token count per segment. Returns ``([pools], tables)`` — each
    pool is (P, g, page_m[, hd]) holding exactly the live pages
    (ceil(len/page_m) per segment) scattered onto a deterministically
    permuted pool, and ``tables`` is the (N, ppn) i32 page table (-1 =
    unallocated). One definition shared by tests/test_paged.py and
    tests/test_differential.py so the "page the dense contents" plumbing
    can't diverge between the structural tests and the harness.
    """
    arrays = [np.asarray(a) for a in arrays]
    n_nodes, cap = arrays[0].shape[0], arrays[0].shape[2]
    ppn = cap // page_m
    needed = [-(-int(m) // page_m) for m in node_lens]
    num_pages = max(sum(needed), 1) + extra_pages
    perm = np.random.RandomState(perm_seed).permutation(num_pages)
    tables = np.full((n_nodes, ppn), -1, np.int32)
    pools = [np.zeros((num_pages,) + a.shape[1:2] + (page_m,) + a.shape[3:],
                      a.dtype) for a in arrays]
    nxt = 0
    for nid in range(n_nodes):
        for j in range(needed[nid]):
            pid = int(perm[nxt])
            nxt += 1
            tables[nid, j] = pid
            sl = slice(j * page_m, (j + 1) * page_m)
            for pool, a in zip(pools, arrays):
                pool[pid] = a[nid, :, sl]
    return [jnp.asarray(p) for p in pools], jnp.asarray(tables)


# ---------------------------------------------------------------------------
# Structural no-HBM-spill assertions (shared by all fused-kernel tests)
# ---------------------------------------------------------------------------

def collect_pallas_calls(jaxpr):
    """All pallas_call eqns in a jaxpr, recursing into sub-jaxprs."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn)
        for v in eqn.params.values():
            if isinstance(v, ClosedJaxpr):
                calls += collect_pallas_calls(v.jaxpr)
            elif isinstance(v, Jaxpr):
                calls += collect_pallas_calls(v)
    return calls


def assert_no_hbm_spill(jaxpr, *, out_dtype, hd=None, q8=False,
                        fresh=False):
    """The fused-decode structural guarantee, in one place:

      * exactly ONE pallas_call in the computation;
      * its only output is the normalized attention result in the query
        dtype — no fp32 (acc, m, l) partials or logits ever reach HBM;
      * for quantized kernels (``q8=True``): the context K/V enter the
        kernel exclusively as int8 (exactly two int8 operands) and the only
        FLOAT operands carrying a head_dim axis are q + the bf16 decode arm
        (3 tensors) — i.e. no dequantized K_c/V_c buffer is ever an HBM
        operand. Callers must pick test shapes with m_c != hd and hd != 128
        so scale vectors / lane-replicated masks can't alias the check.
      * ``fresh=True`` (the packed work-queue kernels): the prefill-chunk
        K/V envelopes are two additional FULL-dtype operands by design
        (fresh tiles are never quantized mid-prefill), so the q8 float-hd
        allowance becomes 5 = q + bf16 decode arm + bf16 fresh K/V.

    Returns the single pallas_call eqn for any kernel-specific follow-ups.
    """
    calls = collect_pallas_calls(jaxpr)
    assert len(calls) == 1, f"expected ONE pallas_call, got {len(calls)}"
    call = calls[0]
    outs = call.outvars
    assert len(outs) == 1, f"fused kernel must write only the output: {outs}"
    assert outs[0].aval.dtype == out_dtype, outs[0].aval
    if q8:
        assert hd is not None, "q8 structural check needs the head_dim"
        in_avals = [v.aval for v in call.invars]
        n_int8 = sum(a.dtype == jnp.int8 for a in in_avals)
        assert n_int8 == 2, f"context K/V must enter as int8: {in_avals}"
        float_hd = [a for a in in_avals
                    if a.dtype != jnp.int8 and a.ndim >= 1
                    and a.shape[-1] == hd]
        want = 5 if fresh else 3
        assert len(float_hd) == want, \
            f"only q + bf16 decode arm{' + fresh K/V' if fresh else ''} " \
            f"may carry head_dim: {float_hd}"
    return call
