"""Validate the trip-count-aware HLO cost analyzer against ground truth:
scanned module cost ~= unrolled module cost ~= analytic GEMM flops."""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.launch.hlo_cost import analyze


def _body(x, w):
    return jnp.tanh(x @ w), None


def f_scan(x, ws):
    y, _ = lax.scan(_body, x, ws)
    return y


def f_unroll(x, ws):
    for i in range(ws.shape[0]):
        x, _ = _body(x, ws[i])
    return x


def test_scan_trip_count_correction():
    L, d = 8, 64
    x = jax.ShapeDtypeStruct((d, d), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
    scanned = jax.jit(f_scan).lower(x, ws).compile()
    unrolled = jax.jit(f_unroll).lower(x, ws).compile()

    analytic = 2.0 * L * d * d * d  # L matmuls
    xla_scan = scanned.cost_analysis()["flops"]
    ours_scan = analyze(scanned.as_text())["flops"]
    ours_unroll = analyze(unrolled.as_text())["flops"]

    # XLA undercounts the scan by ~L; ours does not.
    assert xla_scan < analytic / 2, (xla_scan, analytic)
    assert abs(ours_scan - analytic) / analytic < 0.2, (ours_scan, analytic)
    assert abs(ours_unroll - analytic) / analytic < 0.2, (ours_unroll, analytic)
    # scanned ~= unrolled under our analyzer
    assert abs(ours_scan - ours_unroll) / ours_unroll < 0.25


def test_bytes_scale_with_trip_count():
    d = 64
    x = jax.ShapeDtypeStruct((d, d), jnp.float32)
    for L in (4, 16):
        ws = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
        c = jax.jit(f_scan).lower(x, ws).compile()
        b = analyze(c.as_text())["bytes"]
        # weights alone are L*d*d*4 bytes; must be counted at least once each
        assert b >= L * d * d * 4, (L, b)


def test_dot_general_batched():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32)
    c = jax.jit(f).lower(a, b).compile()
    ours = analyze(c.as_text())["flops"]
    analytic = 2 * 4 * 32 * 64 * 16
    assert abs(ours - analytic) / analytic < 0.1, (ours, analytic)
