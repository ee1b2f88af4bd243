"""Multi-prefix forest decoding + continuous-batching serve loop.

Covers the tentpole acceptance criteria:
  * grouped caches (bf16 + int8): write_context admission, slot
    assignment/reuse, layout parity, spec surfaces;
  * G > 1 end-to-end: each group's greedy tokens match a per-group
    single-prefix ServeEngine.generate run — bf16 AND int8, einsum AND
    grouped-kernel decode;
  * the decode dispatch compiles ONCE across admit/retire events;
  * continuous-batching edge cases: EOS retirement inside the scan,
    EOS-at-step-0, admit-into-retired-slot reuse;
  * structural no-HBM-spill for the grouped bf16 kernel (the q8 twin is in
    tests/test_fused_q8.py) and grouped sharding specs on an SPMD mesh;
  * per-group IO accounting (core.io_model.forest_decode_io_bytes).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_no_hbm_spill, make_decode_case
from repro.configs import ForestConfig, ServeConfig, get_config, reduced_config
from repro.core.kv_cache import GroupedBifurcatedCache
from repro.core.policy import BifurcationPolicy
from repro.core.quantized import GroupedQuantBifurcatedCache
from repro.models import get_model
from repro.runtime.serve import ForestServeEngine, ServeEngine

pytestmark = pytest.mark.slow  # CI runs the slow tier in its own step

CFG = reduced_config(get_config("internlm2-1.8b"))
MODEL = get_model(CFG)
PARAMS = MODEL.init(jax.random.PRNGKey(0))
RNG = np.random.RandomState(0)
CTX_A = jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 24)))
CTX_B = jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 17)))
CTX_C = jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 9)))


def _forest(n_groups=2, slots=5, cache_dtype="bfloat16", use_kernel=False,
            **kw):
    fcfg = ForestConfig(n_groups=n_groups, slots=slots, ctx_capacity=32,
                        decode_capacity=16, temperature=0.0,
                        cache_dtype=cache_dtype, use_kernel=use_kernel, **kw)
    return ForestServeEngine(MODEL, CFG, fcfg)


def _single(ctx, batch, cache_dtype="bfloat16", use_kernel=False, n_steps=8):
    scfg = ServeConfig(batch=batch, decode_capacity=16, temperature=0.0,
                       top_p=1.0, bifurcated=True, use_kernel=use_kernel,
                       cache_dtype=cache_dtype)
    pol = BifurcationPolicy(enabled=True, min_io_saving_bytes=0, min_batch=1)
    eng = ServeEngine(MODEL, CFG, scfg, policy=pol)
    return eng.generate(PARAMS, ctx, n_steps=n_steps,
                        key=jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Grouped caches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["gmk", "mgk"])
def test_grouped_cache_write_context_and_lens(layout):
    cache = GroupedBifurcatedCache.init(2, 3, 4, 32, 8, 2, 16,
                                        ctx_layout=layout)
    k = jnp.ones((2, 20, 2, 16), jnp.float32)
    cache = cache.write_context(k, k * 2, 1)
    assert int(cache.ctx_lens[1]) == 20 and int(cache.ctx_lens[0]) == 0
    seg = cache.k_ctx[:, 1]
    live = seg[:, :, :20] if layout == "gmk" else seg[:, :20]
    dead = seg[:, :, 20:] if layout == "gmk" else seg[:, 20:]
    assert float(jnp.min(jnp.abs(live))) > 0          # segment written
    assert float(jnp.max(jnp.abs(dead))) == 0         # capacity tail zero
    assert float(jnp.max(jnp.abs(cache.k_ctx[:, 0]))) == 0  # others intact


def test_grouped_cache_assign_slots_wipes_stale_decode_arm():
    cache = GroupedBifurcatedCache.init(1, 2, 4, 16, 8, 2, 16)
    cache = dataclasses.replace(
        cache, k_dec=jnp.ones_like(cache.k_dec),
        dec_lens=jnp.full((4,), 5, jnp.int32),
        group_ids=jnp.asarray([0, 0, 1, 1], jnp.int32))
    mask = jnp.asarray([False, True, True, False])
    cache = cache.assign_slots(mask, 1)
    np.testing.assert_array_equal(np.asarray(cache.group_ids), [0, 1, 1, 1])
    np.testing.assert_array_equal(np.asarray(cache.dec_lens), [5, 0, 0, 5])
    assert float(jnp.max(jnp.abs(cache.k_dec[:, 1]))) == 0   # wiped
    assert float(jnp.min(jnp.abs(cache.k_dec[:, 0]))) == 1   # kept


@pytest.mark.parametrize("fam", [GroupedBifurcatedCache,
                                 GroupedQuantBifurcatedCache])
def test_grouped_cache_spec_matches_init(fam):
    spec = fam.spec(2, 3, 4, 32, 8, 2, 16)
    real = fam.init(2, 3, 4, 32, 8, 2, 16)
    assert jax.tree.structure(spec) == jax.tree.structure(real)
    for s, r in zip(jax.tree.leaves(spec), jax.tree.leaves(real)):
        assert s.shape == r.shape and s.dtype == r.dtype
    assert spec.n_groups == 3 and spec.context_capacity == 32
    assert spec.n_slots == 4 and spec.decode_capacity == 8


def test_grouped_quant_cache_quantizes_at_admission():
    cache = GroupedQuantBifurcatedCache.init(2, 2, 4, 32, 8, 2, 16)
    rng = np.random.RandomState(3)
    k = jnp.asarray(rng.randn(2, 20, 2, 16), jnp.float32)
    cache = cache.write_context(k, k, 0)
    assert cache.k_ctx.dtype == jnp.int8
    assert int(cache.ctx_lens[0]) == 20
    # k scales carry the logit fold: smaller than the raw v scales
    ks = np.asarray(cache.k_scale[:, 0, :, :20])
    vs = np.asarray(cache.v_scale[:, 0, :, :20])
    assert ks.min() > 0 and np.all(ks < vs)
    np.testing.assert_allclose(ks * 16**0.5, vs, rtol=1e-5)


# ---------------------------------------------------------------------------
# Structural + sharding
# ---------------------------------------------------------------------------

def test_grouped_bf16_kernel_no_hbm_spill():
    """The grouped (forest) bf16 kernel keeps the fused-kernel guarantee:
    ONE pallas_call, one normalized bf16 output, no fp32 partials."""
    from repro.kernels.ops import grouped_bifurcated_decode_attention

    case = make_decode_case(2, 2, 64, 8, g=2, hd=32, dtype=jnp.bfloat16,
                            seed=1, full_mask=True)
    gids = jnp.zeros((2,), jnp.int32)
    clens = jnp.asarray([64], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: grouped_bifurcated_decode_attention(
            *a, interpret=True, ctx_layout="mgk")
    )(case["q"], case["kc"][None], case["vc"][None], gids, clens,
      case["kd"], case["vd"], case["mask"]).jaxpr
    assert_no_hbm_spill(jaxpr, out_dtype=jnp.bfloat16)


@pytest.mark.parametrize("ctx_quant", ["none", "int8"])
@pytest.mark.parametrize("layout", ["gmk", "mgk"])
def test_forest_cache_pspec_tree_layout_aware(ctx_quant, layout):
    from repro.core.quantized import forest_cache_family
    from repro.launch.steps import cache_pspec_tree

    fam = forest_cache_family(ctx_quant)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    spec = fam.spec(2, 2, 4, 64, 8, 2, 16, ctx_layout=layout)
    ps = cache_pspec_tree(mesh, spec)
    ctx_dim = 3 if layout == "gmk" else 2
    assert ps.k_ctx[ctx_dim] == "model"          # ctx seq dim sharded
    assert all(ax is None for i, ax in enumerate(ps.k_ctx) if i != ctx_dim)
    assert ps.k_dec[2] == "model"
    if ctx_quant == "int8":
        assert ps.k_scale[ctx_dim] == "model"    # scales follow the values
    assert ps.ctx_lens == jax.sharding.PartitionSpec()


def test_forest_decode_spmd_compiles_on_8_devices():
    """Grouped decode_step lowers + compiles under an 8-device (2, 4) SPMD
    mesh with the forest cache sharded by launch.steps.cache_pspec_tree
    (context sequence dim over "model"), bf16 AND int8 families."""
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
                io = S.forest_decode_cache_specs(
                    cfg, model, slots=4, n_groups=2, ctx_capacity=64,
                    dec_capacity=8, ctx_quant=quant)
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
    assert out["int8"] < out["none"]     # int8 segments shrink the args


# ---------------------------------------------------------------------------
# End-to-end acceptance: G > 1 forest == per-group single-prefix engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache_dtype,use_kernel", [
    ("bfloat16", False), ("bfloat16", True),
    ("int8", False), ("int8", True),
])
def test_forest_matches_per_group_single_prefix(cache_dtype, use_kernel):
    """ISSUE acceptance: for G > 1 each group's greedy tokens are IDENTICAL
    to a per-group single-prefix ServeEngine.generate run (bf16 and int8,
    einsum and grouped-kernel decode paths)."""
    eng = _forest(cache_dtype=cache_dtype, use_kernel=use_kernel)
    st = eng.init_state()
    st, slots_a = eng.admit(PARAMS, st, CTX_A, 3)
    st, slots_b = eng.admit(PARAMS, st, CTX_B, 2)
    st = eng.step_chunk(PARAMS, st, 7)
    r_a = _single(CTX_A, 3, cache_dtype, use_kernel)
    r_b = _single(CTX_B, 2, cache_dtype, use_kernel)
    np.testing.assert_array_equal(
        np.stack([eng.outputs[s] for s in slots_a]), np.asarray(r_a.tokens))
    np.testing.assert_array_equal(
        np.stack([eng.outputs[s] for s in slots_b]), np.asarray(r_b.tokens))


def test_forest_decode_dispatch_compiles_once_across_admit_retire():
    """ISSUE acceptance: admission state is data, not shape — the jitted
    decode chunk compiles exactly once across admit / step / retire /
    re-admit cycles."""
    eng = _forest(n_groups=2, slots=4)
    st = eng.init_state()
    st, slots_a = eng.admit(PARAMS, st, CTX_A, 2)
    st = eng.step_chunk(PARAMS, st, 4)
    st, slots_b = eng.admit(PARAMS, st, CTX_B, 2)
    st = eng.step_chunk(PARAMS, st, 4)
    # force-retire group A's slots, free its segment, admit a new request
    # into the SAME slots + segment, keep decoding
    st = dataclasses.replace(
        st, active=st.active & ~jnp.isin(jnp.arange(4),
                                         jnp.asarray(slots_a)))
    assert eng.retire_groups(st) != []
    st, slots_c = eng.admit(PARAMS, st, CTX_C, 2)
    assert set(slots_c) == set(slots_a)          # retired slots reused
    st = eng.step_chunk(PARAMS, st, 4)
    assert eng.decode_dispatches == 3
    assert eng._chunk._cache_size() == 1         # ONE compile for them all


def test_forest_readmitted_slots_decode_correctly():
    """Admit-into-retired-slot reuse: after a group retires, a new request
    admitted into its slots produces the same tokens as a fresh engine
    (stale decode KVs are wiped by assign_slots)."""
    eng = _forest(n_groups=2, slots=4)
    st = eng.init_state()
    st, slots_a = eng.admit(PARAMS, st, CTX_A, 2)
    st = eng.step_chunk(PARAMS, st, 5)
    st = dataclasses.replace(
        st, active=st.active & ~jnp.isin(jnp.arange(4),
                                         jnp.asarray(slots_a)))
    eng.retire_groups(st)
    st, slots_c = eng.admit(PARAMS, st, CTX_C, 2)
    st = eng.step_chunk(PARAMS, st, 7)
    ref = _single(CTX_C, 2)
    np.testing.assert_array_equal(
        np.stack([eng.outputs[s] for s in slots_c]), np.asarray(ref.tokens))


def test_forest_eos_retires_slot_inside_scan():
    """EOS retirement lives INSIDE the jitted scan carry: a slot that
    samples eos_token stops emitting (pad from then on), its step counter
    freezes, and other slots are unaffected."""
    eng0 = _forest()          # find the greedy token stream first
    st0 = eng0.init_state()
    st0, slots0 = eng0.admit(PARAMS, st0, CTX_A, 2)
    st0 = eng0.step_chunk(PARAMS, st0, 6)
    stream = eng0.outputs[slots0[0]]
    eos = stream[3]           # retire after 3 post-prefill steps
    k_eos = stream.index(eos)  # first emission of that token (may be < 3)

    eng = _forest(eos_token=int(eos), pad_token=-7)
    st = eng.init_state()
    st, slots = eng.admit(PARAMS, st, CTX_A, 2)
    st = eng.step_chunk(PARAMS, st, 6)
    out = eng.outputs[slots[0]]
    assert out == stream[:k_eos + 1]             # emitted up to & incl. EOS
    assert not bool(st.active[slots[0]])         # retired in-scan
    assert int(st.steps[slots[0]]) == k_eos      # step counter frozen
    # retirement happened mid-chunk, with shapes unchanged and one compile
    assert eng._chunk._cache_size() == 1


def test_forest_eos_at_step_0_retires_before_decode():
    """A first token (sampled from the prefill logits) equal to eos_token
    retires the slot before it ever enters the decode loop."""
    probe = _forest()
    st = probe.init_state()
    st, slots = probe.admit(PARAMS, st, CTX_A, 2)
    first = probe.outputs[slots[0]][0]

    eng = _forest(eos_token=int(first))
    st = eng.init_state()
    st, slots = eng.admit(PARAMS, st, CTX_A, 2)
    assert not bool(st.active[slots[0]])         # EOS at step 0
    assert eng.outputs[slots[0]] == [first]
    st = eng.step_chunk(PARAMS, st, 4)
    assert eng.outputs[slots[0]] == [first]      # nothing further emitted
    # the whole group retires once every slot has hit EOS
    if not any(bool(st.active[s]) for s in slots):
        assert eng.retire_groups(st) != []


def test_forest_eos_slot_of_live_group_not_reused_until_retire():
    """An EOS'd slot whose group is still live keeps its finished output
    readable: free_slots excludes it (admitting into it would clobber the
    host-side result lists) until retire_groups frees the whole group."""
    probe = _forest()
    st = probe.init_state()
    st, slots = probe.admit(PARAMS, st, CTX_A, 2)
    first = probe.outputs[slots[0]][0]

    eng = _forest(n_groups=3, eos_token=int(first))
    st = eng.init_state()
    st, slots = eng.admit(PARAMS, st, CTX_A, 2)
    # greedy sampling from the shared prefill logits: BOTH fanned-out slots
    # sample `first` and EOS at step 0 — the group is fully inactive but
    # NOT yet retired, so its finished outputs must stay readable
    assert not any(bool(st.active[s]) for s in slots)
    free = eng.free_slots(st)
    assert all(s not in free for s in slots)      # NOT reusable yet
    st, slots_b = eng.admit(PARAMS, st, CTX_B, 2)
    assert not set(slots) & set(slots_b)          # admit used fresh slots
    assert eng.outputs[slots[0]] == [first]       # finished output intact
    # after the whole group retires, the slot becomes reusable
    st = dataclasses.replace(
        st, active=st.active & ~jnp.isin(jnp.arange(eng.fcfg.slots),
                                         jnp.asarray(slots)))
    eng.retire_groups(st)
    assert slots[0] in eng.free_slots(st)


def test_forest_step_chunk_guards_decode_capacity():
    """Decoding past a live slot's decode capacity would silently clamp
    the KV write at the last cache slot (corrupting that slot's decode
    arm) — step_chunk refuses up front instead."""
    eng = _forest()                     # decode_capacity=16
    st = eng.init_state()
    st, slots = eng.admit(PARAMS, st, CTX_A, 2)
    st = eng.step_chunk(PARAMS, st, 10)
    with pytest.raises(RuntimeError, match="decode_capacity"):
        eng.step_chunk(PARAMS, st, 7)   # deepest live slot at 10: 10+7 > 16
    st = eng.step_chunk(PARAMS, st, 6)  # exactly at capacity is fine
    assert all(len(eng.outputs[s]) == 17 for s in slots)
    # retired slots don't count: deactivate, then long chunks are legal
    st = dataclasses.replace(st, active=jnp.zeros_like(st.active))
    st = eng.step_chunk(PARAMS, st, 7)


def test_forest_admit_exhaustion_raises():
    eng = _forest(n_groups=1, slots=2)
    st = eng.init_state()
    st, _ = eng.admit(PARAMS, st, CTX_A, 2)
    with pytest.raises(RuntimeError):
        eng.admit(PARAMS, st, CTX_B, 1)          # no free segment
    eng2 = _forest(n_groups=2, slots=2)
    st2 = eng2.init_state()
    st2, _ = eng2.admit(PARAMS, st2, CTX_A, 2)
    with pytest.raises(RuntimeError):
        eng2.admit(PARAMS, st2, CTX_B, 1)        # no free slot


# ---------------------------------------------------------------------------
# Per-group IO accounting
# ---------------------------------------------------------------------------

def test_forest_io_bytes_per_group_accounting():
    from repro.core.io_model import (
        decode_impl_io_bytes,
        forest_decode_io_bytes,
    )

    io = forest_decode_io_bytes(group_sizes=[16, 4], ctx_lens=[4096, 512],
                                c_d=32, g=8, hd=128)
    assert len(io["per_group"]) == 2
    assert io["per_group"][0] > io["per_group"][1]   # longer + wider group
    assert io["total"] == sum(io["per_group"]) + (16 + 4) * 8 * 128 * 2 * 2
    assert io["io_saving"] > 5                       # mixed-batch saving
    # G=1 full population reduces exactly to the single-prefix fused model
    one = forest_decode_io_bytes(group_sizes=[16], ctx_lens=[4096],
                                 c_d=32, g=8, hd=128)
    assert one["total"] == decode_impl_io_bytes(
        b=16, p=1, n=1, m_c=4096, c_d=32, g=8, hd=128, impl="fused")
    # q8 segments halve the dominant (context) term
    q8 = forest_decode_io_bytes(group_sizes=[16, 4], ctx_lens=[4096, 512],
                                c_d=32, g=8, hd=128, impl="grouped_q8")
    assert q8["total"] < io["total"]
    assert q8["io_saving"] > io["io_saving"]
    # padded-envelope accounting (what the CURRENT kernel DMAs: every
    # segment at full capacity, freed segments included) costs more than
    # the live-length model and coincides with it when segments are full
    env = forest_decode_io_bytes(group_sizes=[16, 4, 0],
                                 ctx_lens=[4096, 512, 0],
                                 c_d=32, g=8, hd=128, ctx_capacity=4096)
    assert env["total"] > io["total"]
    assert env["io_saving"] < io["io_saving"]
    full = forest_decode_io_bytes(group_sizes=[16, 4], ctx_lens=[4096, 4096],
                                  c_d=32, g=8, hd=128)
    assert full["total"] == forest_decode_io_bytes(
        group_sizes=[16, 4], ctx_lens=[4096, 4096], c_d=32, g=8, hd=128,
        ctx_capacity=4096)["total"]
