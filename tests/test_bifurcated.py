"""Exactness of bifurcated attention vs standard attention (paper §4.2 /
Appendix E.1), plus the online-softmax (flash) join and SWA clipping."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    bifurcated_attention,
    bifurcated_attention_flash,
    merge_partials,
    multigroup_attention,
)
from repro.core.bifurcated import _partial_softmax
from repro.core.policy import BifurcationPolicy


def make_inputs(rng, b, g, p, n, k, m_c, m_d, dtype=jnp.float32):
    q = jnp.asarray(rng.randn(b, g, p, n, k), dtype)
    kc = jnp.asarray(rng.randn(m_c, g, k), dtype)
    vc = jnp.asarray(rng.randn(m_c, g, k), dtype)
    kd = jnp.asarray(rng.randn(b, m_d, g, k), dtype)
    vd = jnp.asarray(rng.randn(b, m_d, g, k), dtype)
    return q, kc, vc, kd, vd


def reference(q, kc, vc, kd, vd, dec_mask=None, ctx_mask=None):
    b, _, _, _, k = q.shape
    m_c, g = kc.shape[0], kc.shape[1]
    m_d = kd.shape[1]
    K = jnp.concatenate([jnp.broadcast_to(kc[None], (b, m_c, g, k)), kd], axis=1)
    V = jnp.concatenate([jnp.broadcast_to(vc[None], (b, m_c, g, k)), vd], axis=1)
    cm = jnp.ones((m_c,), bool) if ctx_mask is None else ctx_mask
    dm = jnp.ones((b, m_d), bool) if dec_mask is None else dec_mask
    mask = jnp.concatenate([jnp.broadcast_to(cm[None], (b, m_c)), dm], axis=1)
    return multigroup_attention(q, K, V, mask=mask[:, None, None, None, :])


# (b, g, p, n, m_c, m_d) sweep: MHA (p=1), GQA, MQA (g=1), spec-decode n>1
SHAPES = [
    (1, 1, 1, 1, 8, 4),
    (4, 2, 3, 1, 37, 9),
    (8, 1, 8, 1, 64, 16),   # multi-query
    (2, 8, 1, 1, 128, 32),  # multi-head-ish
    (3, 4, 2, 4, 50, 12),   # speculative decoding, n_g = 4 (paper §G)
    (16, 2, 2, 1, 256, 1),
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("flash", [False, True])
def test_exactness_fp32(shape, flash):
    b, g, p, n, m_c, m_d = shape
    rng = np.random.RandomState(hash(shape) % 2**31)
    q, kc, vc, kd, vd = make_inputs(rng, b, g, p, n, 16, m_c, m_d)
    fn = bifurcated_attention_flash if flash else bifurcated_attention
    out = fn(q, kc, vc, kd, vd)
    ref = reference(q, kc, vc, kd, vd)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("flash", [False, True])
def test_exactness_bf16(flash):
    rng = np.random.RandomState(0)
    q, kc, vc, kd, vd = make_inputs(rng, 4, 2, 2, 1, 16, 64, 16, dtype=jnp.bfloat16)
    fn = bifurcated_attention_flash if flash else bifurcated_attention
    out = fn(q, kc, vc, kd, vd).astype(jnp.float32)
    ref = reference(q, kc, vc, kd, vd).astype(jnp.float32)
    np.testing.assert_allclose(out, ref, rtol=3e-2, atol=3e-2)


def test_decode_mask():
    rng = np.random.RandomState(1)
    b, m_d = 4, 12
    q, kc, vc, kd, vd = make_inputs(rng, b, 2, 2, 1, 16, 20, m_d)
    dec_len = 5
    dm = jnp.broadcast_to(jnp.arange(m_d)[None] < dec_len, (b, m_d))
    out = bifurcated_attention(q, kc, vc, kd, vd, decode_mask=dm)
    ref = reference(q, kc, vc, kd, vd, dec_mask=dm)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_context_mask_swa_clipping():
    """Sliding-window clipping of the context arm (danube/mixtral)."""
    rng = np.random.RandomState(2)
    b, m_c, m_d = 3, 40, 8
    q, kc, vc, kd, vd = make_inputs(rng, b, 2, 2, 1, 16, m_c, m_d)
    ctx_mask = jnp.arange(m_c) >= 25  # only trailing window live
    out = bifurcated_attention(q, kc, vc, kd, vd, context_mask=ctx_mask)
    ref = reference(q, kc, vc, kd, vd, ctx_mask=ctx_mask)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_merge_partials_associative():
    """Three-way split (sequence-sharded K_c) == two-way == monolithic."""
    rng = np.random.RandomState(3)
    q, kc, vc, kd, vd = make_inputs(rng, 2, 2, 2, 1, 16, 48, 8)
    scale = 16**-0.5
    lc = jnp.einsum("bgpnk,mgk->bgpnm", q, kc) * scale
    ld = jnp.einsum("bgpnk,bmgk->bgpnm", q, kd) * scale
    parts = []
    for i in range(3):  # context split into 3 shards of 16
        sl = slice(16 * i, 16 * (i + 1))
        parts.append(_partial_softmax(lc[..., sl], vc[sl], batched=False))
    parts.append(_partial_softmax(ld, vd, batched=True))
    out = merge_partials(parts)
    ref = reference(q, kc, vc, kd, vd)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_same_flops_structure():
    """Paper claim: same FLOPs. Count HLO dot FLOPs for both paths."""
    rng = np.random.RandomState(4)
    q, kc, vc, kd, vd = make_inputs(rng, 8, 4, 2, 1, 64, 512, 64)

    def naive(q, kc, vc, kd, vd):
        return reference(q, kc, vc, kd, vd)

    def cost(fn):
        return jax.jit(fn).lower(q, kc, vc, kd, vd).compile().cost_analysis()

    c_bif = cost(bifurcated_attention)
    c_ref = cost(naive)
    f_bif = c_bif["flops"]
    f_ref = c_ref["flops"]
    # identical GEMM flops; small bookkeeping differences allowed (<5%)
    assert abs(f_bif - f_ref) / f_ref < 0.05, (f_bif, f_ref)
    # ... but strictly less HBM traffic for the bifurcated path
    assert c_bif["bytes accessed"] < c_ref["bytes accessed"]


def test_policy_switch():
    pol = BifurcationPolicy()
    # large shared context, decent batch -> bifurcate
    assert pol.should_bifurcate(batch=16, m_c=8192, n_groups=32, head_dim=128)
    # batch 1 -> never
    assert not pol.should_bifurcate(batch=1, m_c=8192, n_groups=32, head_dim=128)
    # tiny workload -> stay fused
    assert not pol.should_bifurcate(batch=2, m_c=16, n_groups=2, head_dim=16)
    # paper Eq. 5-6: saving == g*k*m_c*(b-1) per K and V
    s = pol.io_saving_bytes(batch=4, m_c=100, n_groups=2, head_dim=8, bytes_per_el=2)
    assert s == 2 * 2 * 8 * 100 * 3 * 2


def test_chunked_attention_multi_chunk_exact():
    """Regression: chunk-major vs position-major flattening when n > chunk
    (the nc > 1 case smoke tests don't hit)."""
    from repro.models.blocks import chunked_attention, flash_chunked_attention

    rng = np.random.RandomState(9)
    b, n, h, g, hd = 2, 100, 4, 2, 16
    q = jnp.asarray(rng.randn(b, n, h, hd), jnp.float32)
    k = jnp.asarray(rng.randn(b, n, g, hd), jnp.float32)
    v = jnp.asarray(rng.randn(b, n, g, hd), jnp.float32)
    # monolithic reference
    p = h // g
    qq = q.reshape(b, n, g, p, hd).transpose(0, 2, 3, 1, 4)
    mask = (jnp.arange(n)[:, None] >= jnp.arange(n)[None, :])
    ref = multigroup_attention(qq, k, v, mask=mask[None, None, None])
    ref = ref.transpose(0, 3, 1, 2, 4).reshape(b, n, h, hd)
    for fn, kw in ((chunked_attention, dict(chunk=32)),
                   (flash_chunked_attention, dict(q_chunk=32, kv_chunk=16))):
        out = fn(q, k, v, causal=True, **kw)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
