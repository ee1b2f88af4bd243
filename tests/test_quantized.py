"""Int8 context-KV quantization (core/quantized.py, beyond-paper §Perf):
round-trip accuracy, attention-path accuracy vs the fp path (both layouts,
logit scale pre-folded into k_scale), cache-family layout parity with
BifurcatedCache, and the end-to-end decode path through the model."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core.bifurcated import bifurcated_attention
from repro.core.kv_cache import BifurcatedCache
from repro.core.quantized import (
    QuantBifurcatedCache,
    bifurcated_attention_q8,
    dequantize_ctx,
    quantize_ctx,
)
from repro.models import get_model


def test_quantize_roundtrip_error_bounded():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 4, 32) * 2.0, jnp.float32)
    q, s = quantize_ctx(x)
    back = dequantize_ctx(q, s)
    # symmetric per-(token, head) int8: error bounded by scale/2 per element
    max_err = float(jnp.max(jnp.abs(back - x)))
    assert max_err <= float(jnp.max(s)) * 0.51


def test_quantize_fold_scale_prescales():
    """The logit scale folds into the returned scales (satellite: one fewer
    broadcast multiply per block on the hot loop)."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(16, 2, 32), jnp.float32)
    q0, s0 = quantize_ctx(x)
    q1, s1 = quantize_ctx(x, fold_scale=0.125)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0) * 0.125,
                               rtol=1e-6)


def test_q8_attention_close_to_fp():
    rng = np.random.RandomState(1)
    b, g, p, hd, m_c, c_d = 4, 2, 2, 32, 128, 16
    q = jnp.asarray(rng.randn(b, g, p, 1, hd), jnp.float32)
    kc = jnp.asarray(rng.randn(m_c, g, hd), jnp.float32)
    vc = jnp.asarray(rng.randn(m_c, g, hd), jnp.float32)
    kd = jnp.asarray(rng.randn(b, c_d, g, hd), jnp.float32)
    vd = jnp.asarray(rng.randn(b, c_d, g, hd), jnp.float32)
    # k_scale carries the attention logit scale pre-folded
    kq, ks = quantize_ctx(kc, fold_scale=hd**-0.5)
    vq, vs = quantize_ctx(vc)
    out_q = bifurcated_attention_q8(q, kq, vq, ks, vs, kd, vd)
    out_f = bifurcated_attention(q, kc, vc, kd, vd)
    np.testing.assert_allclose(out_q, out_f, rtol=0.05, atol=0.05)


def test_q8_attention_gmk_layout_matches_mgk():
    """Head-major "gmk" int8 context + "gmk"-shaped scales produce identical
    results to the sequence-major reference layout."""
    rng = np.random.RandomState(3)
    b, g, p, hd, m_c, c_d = 3, 2, 2, 32, 96, 8
    q = jnp.asarray(rng.randn(b, g, p, 1, hd), jnp.float32)
    kc = jnp.asarray(rng.randn(m_c, g, hd), jnp.float32)
    vc = jnp.asarray(rng.randn(m_c, g, hd), jnp.float32)
    kd = jnp.asarray(rng.randn(b, c_d, g, hd), jnp.float32)
    vd = jnp.asarray(rng.randn(b, c_d, g, hd), jnp.float32)
    kq, ks = quantize_ctx(kc, fold_scale=hd**-0.5)
    vq, vs = quantize_ctx(vc)
    out_mgk = bifurcated_attention_q8(q, kq, vq, ks, vs, kd, vd,
                                      ctx_layout="mgk")
    out_gmk = bifurcated_attention_q8(
        q, kq.transpose(1, 0, 2), vq.transpose(1, 0, 2), ks.T, vs.T, kd, vd,
        ctx_layout="gmk")
    np.testing.assert_allclose(out_mgk, out_gmk, rtol=1e-6, atol=1e-6)


def test_quant_cache_layout_aware_and_spec_parity():
    """Satellite: context_len is layout-aware and spec/from_prefill expose
    the same ctx_layout parameter surface as BifurcatedCache (drop-in
    interchangeable cache families)."""
    L, b, m_c, cd, g, hd = 2, 3, 24, 8, 2, 16
    for layout in ("gmk", "mgk"):
        spec_q = QuantBifurcatedCache.spec(L, b, m_c, cd, g, hd,
                                           ctx_layout=layout)
        spec_f = BifurcatedCache.spec(L, b, m_c, cd, g, hd,
                                      ctx_layout=layout)
        assert spec_q.context_len == spec_f.context_len == m_c
        assert spec_q.decode_capacity == spec_f.decode_capacity == cd
        assert spec_q.ctx_layout == layout
        assert spec_q.k_ctx.dtype == jnp.int8
        # int8 values carry the SAME axis order as the fp cache; scales drop
        # the trailing hd axis
        assert spec_q.k_ctx.shape == spec_f.k_ctx.shape
        assert spec_q.k_scale.shape == spec_f.k_ctx.shape[:-1]

    rng = np.random.RandomState(5)
    kf = jnp.asarray(rng.randn(L, m_c, g, hd), jnp.float32)
    vf = jnp.asarray(rng.randn(L, m_c, g, hd), jnp.float32)
    c_gmk = QuantBifurcatedCache.from_prefill(kf, vf, b, cd, ctx_layout="gmk")
    c_mgk = QuantBifurcatedCache.from_prefill(kf, vf, b, cd, ctx_layout="mgk")
    assert c_gmk.context_len == c_mgk.context_len == m_c
    assert c_gmk.k_ctx.shape == (L, g, m_c, hd)
    assert c_mgk.k_ctx.shape == (L, m_c, g, hd)
    # same quantization, different axis order
    np.testing.assert_array_equal(
        np.asarray(c_gmk.k_ctx), np.asarray(c_mgk.k_ctx.transpose(0, 2, 1, 3)))
    np.testing.assert_allclose(
        np.asarray(c_gmk.k_scale), np.asarray(c_mgk.k_scale.transpose(0, 2, 1)),
        rtol=1e-6)
    # k_scale is pre-folded with hd**-0.5; v_scale is not
    kq_raw, ks_raw = quantize_ctx(kf)
    np.testing.assert_allclose(np.asarray(c_mgk.k_scale),
                               np.asarray(ks_raw) * hd**-0.5, rtol=1e-6)


def test_decode_impl_io_bytes_quant_acceptance():
    """Acceptance: the modelled per-layer-step HBM traffic of the fused q8
    path undercuts bf16 fused >= 1.6x at (b=16, m_c=4096), and the
    context-arm-only traffic drops ~2x at production hd."""
    from repro.core.io_model import decode_impl_io_bytes, quantized_ctx_bytes

    kw = dict(b=16, p=1, n=1, m_c=4096, c_d=32, g=8, hd=64)
    io = {impl: decode_impl_io_bytes(impl=impl, **kw)
          for impl in ("einsum", "einsum_q8", "two_pass", "fused", "fused_q8")}
    assert io["fused"] / io["fused_q8"] >= 1.6, io
    assert io["fused_q8"] < io["fused"] < io["two_pass"] < io["einsum"]
    assert io["einsum_q8"] < io["einsum"]
    # context arm alone: 2*hd bytes vs hd + 4 (f32 scale) per (token, head)
    ctx_bf16 = 2 * 8 * 4096 * 128 * 2
    assert ctx_bf16 / quantized_ctx_bytes(m_c=4096, g=8, hd=128) > 1.9


def test_quant_cache_pspec_tree_layout_aware():
    """Sharding specs shard the context sequence dim of the int8 values AND
    the scale leaves identically under both layouts."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.launch.steps import cache_pspec_tree

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    for layout in ("gmk", "mgk"):
        spec = QuantBifurcatedCache.spec(2, 4, 32, 8, 2, 16,
                                         ctx_layout=layout)
        ps = cache_pspec_tree(mesh, spec)
        assert ps.ctx_layout == layout
        if layout == "gmk":   # (L, g, m_c, hd) / (L, g, m_c)
            assert ps.k_ctx == P(None, None, "model", None)
            assert ps.k_scale == P(None, None, "model")
        else:                 # (L, m_c, g, hd) / (L, m_c, g)
            assert ps.k_ctx == P(None, "model", None, None)
            assert ps.k_scale == P(None, "model", None)


def test_model_decode_with_q8_cache():
    cfg = reduced_config(get_config("internlm2-1.8b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    b, m_c = 3, 24
    ctx = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, m_c)))
    cont = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, 3)))
    _, c1 = model.prefill(params, ctx, None)

    cache_fp = BifurcatedCache.from_prefill(c1.k[:, 0], c1.v[:, 0], b, 16,
                                            dtype=c1.k.dtype,
                                            ctx_layout=cfg.ctx_layout)
    cache_q8 = QuantBifurcatedCache.from_prefill(
        c1.k[:, 0].astype(jnp.float32), c1.v[:, 0].astype(jnp.float32), b, 16,
        ctx_layout=cfg.ctx_layout)
    scale = None
    for t in range(3):
        lf, cache_fp = model.decode_step(params, cache_fp, cont[:, t:t + 1], None)
        lq, cache_q8 = model.decode_step(params, cache_q8, cont[:, t:t + 1], None)
        scale = float(jnp.max(jnp.abs(lf)))
        err = float(jnp.max(jnp.abs(lf - lq)))
        assert err < 0.1 * max(scale, 1.0), (t, err, scale)
    # int8 context cache halves the bytes (modulo the per-(token,head)
    # scale overhead: 4/hd — 25% at this toy hd=16, 3% at production hd=128)
    fp_bytes = cache_fp.k_ctx.size * 2
    q8_bytes = cache_q8.k_ctx.size * 1 + cache_q8.k_scale.size * 4
    assert q8_bytes < 0.7 * fp_bytes
    hd = 128  # production head dim
    assert (hd + 4) / (2 * hd) < 0.52


def test_encdec_decode_with_q8_cache():
    """Whisper-style enc-dec: int8 self-attention context arm via
    ctx_quant="int8" tracks the bf16 bifurcated path."""
    cfg = reduced_config(get_config("whisper-medium"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(6)
    b = 3
    ctx = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 12)))
    frames = jnp.asarray(rng.randn(1, 16, cfg.d_model) * 0.02, jnp.float32)
    cont = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, 2)))
    _, c_fp = model.prefill(params, ctx, None, frames=frames, bifurcated=True,
                            sample_batch=b)
    _, c_q8 = model.prefill(params, ctx, None, frames=frames, bifurcated=True,
                            sample_batch=b, ctx_quant="int8")
    assert isinstance(c_q8["self"], QuantBifurcatedCache)
    assert c_q8["self"].k_ctx.dtype == jnp.int8
    for t in range(2):
        lf, c_fp = model.decode_step(params, c_fp, cont[:, t:t + 1], None)
        lq, c_q8 = model.decode_step(params, c_q8, cont[:, t:t + 1], None)
        scale = float(jnp.max(jnp.abs(lf)))
        assert float(jnp.max(jnp.abs(lf - lq))) < 0.1 * max(scale, 1.0)
    assert isinstance(c_q8["self"], QuantBifurcatedCache)  # survives decode


def test_hybrid_decode_with_q8_cache():
    """Zamba2-style hybrid: the shared attention block's context arm
    quantizes via ctx_quant="int8" and tracks the bf16 path."""
    cfg = reduced_config(get_config("zamba2-7b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(8)
    ctx = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 12)))
    cont = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 2)))
    _, c_fp = model.prefill(params, ctx, None, bifurcated=True)
    _, c_q8 = model.prefill(params, ctx, None, bifurcated=True,
                            ctx_quant="int8")
    assert isinstance(c_q8["attn"], QuantBifurcatedCache)
    for t in range(2):
        lf, c_fp = model.decode_step(params, c_fp, cont[:, t:t + 1], None)
        lq, c_q8 = model.decode_step(params, c_q8, cont[:, t:t + 1], None)
        scale = float(jnp.max(jnp.abs(lf)))
        assert float(jnp.max(jnp.abs(lf - lq))) < 0.1 * max(scale, 1.0)
    assert isinstance(c_q8["attn"], QuantBifurcatedCache)


def test_hybrid_serve_engine_int8_cache_not_ignored():
    """Regression: ServeEngine(cache_dtype="int8") must reach the hybrid
    family too — prefill_shared injects ctx_quant and the broadcast keeps
    the quantized cache family."""
    from repro.configs import ServeConfig
    from repro.core.policy import BifurcationPolicy
    from repro.runtime.serve import ServeEngine

    cfg = reduced_config(get_config("zamba2-7b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ctx = jnp.asarray(np.random.RandomState(9).randint(
        0, cfg.vocab_size, (1, 12)))
    scfg = ServeConfig(batch=3, decode_capacity=24, temperature=0.0,
                       cache_dtype="int8")
    eng = ServeEngine(model, cfg, scfg,
                      policy=BifurcationPolicy(enabled=True,
                                               min_io_saving_bytes=0))
    _, cache = eng.prefill_shared(params, ctx, 3)
    assert isinstance(cache["attn"], QuantBifurcatedCache)
    assert cache["attn"].k_ctx.dtype == jnp.int8
    assert cache["attn"].k_dec.shape[1] == 3  # decode arm broadcast to batch
    # the decode arm is sized from the SERVE config, not cfg.decode_capacity
    assert cache["attn"].decode_capacity == scfg.decode_capacity
    r = eng.generate(params, ctx, n_steps=3, key=jax.random.PRNGKey(0))
    assert r.tokens.shape == (3, 3)
    assert np.isfinite(np.asarray(r.logprobs)).all()


def test_model_decode_q8_kernel_impl_matches_einsum():
    """decode_step(impl="kernel") on a quantized cache routes through the
    fused q8 Pallas kernel and matches the q8 einsum reference path."""
    cfg = reduced_config(get_config("internlm2-1.8b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    b, m_c = 3, 24
    ctx = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, m_c)))
    tok = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, 1)))
    _, c1 = model.prefill(params, ctx, None)
    cache = QuantBifurcatedCache.from_prefill(
        c1.k[:, 0].astype(jnp.float32), c1.v[:, 0].astype(jnp.float32), b, 16,
        ctx_layout=cfg.ctx_layout)
    lk, ck = model.decode_step(params, cache, tok, None, impl="kernel")
    le, ce = model.decode_step(params, cache, tok, None, impl="einsum")
    assert isinstance(ck, QuantBifurcatedCache)
    assert ck.ctx_layout == cfg.ctx_layout
    scale = float(jnp.max(jnp.abs(le)))
    assert float(jnp.max(jnp.abs(lk - le))) < 0.05 * max(scale, 1.0)
