"""Compile the main-path decode kernels for a TPU v5e that is described, not
attached, so Mosaic refuses here what it would refuse on the chip (block
shapes off the (8, 128) tiling, fast-memory limits) — a refusal that
interpret mode never shows.

Widths are internlm2-1.8b's: 8 kv heads, 2 query heads per kv head,
head_dim 128, 32 decode slots, a 3-level trie, pages of 64, 128 and 256
tokens. Each dispatcher is compiled with ``interpret=False`` passed
explicitly (the CPU is the default backend here) and the compiled program
must hold the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never while the module
is imported: only one process at a time may load the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

G, P_Q, HD = 8, 2, 128          # kv heads, query heads per kv head, head_dim
SLOTS, C_D, DEPTH = 32, 64, 3   # decode slots, decode capacity, trie depth
N_NODES, M_C = 8, 1024          # trie nodes / groups, tokens per node
CHUNK_ROWS = 256                # piggybacked prefill chunk rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """Shape factory placed on one described chip. The persistent compile
    cache is off meanwhile: a chip compile written to it could not be read
    back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_has_kernel(fn, *args):
    text = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile().as_text()
    return "tpu_custom_call" in text


def _decode_arm(sds):
    q = sds((SLOTS, G, P_Q, 1, HD), jnp.bfloat16)
    kd = sds((SLOTS, C_D, G, HD), jnp.bfloat16)
    return q, kd, kd, sds((SLOTS, C_D), jnp.bool_)


def _dense_args(name, sds):
    """Dense-context dispatchers: single prefix, grouped (forest), tree."""
    bf, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32
    q, kd, vd, dm = _decode_arm(sds)
    q8 = name.endswith("_q8")
    kv_dt = i8 if q8 else bf
    if name.startswith("fused"):
        kv = sds((G, M_C, HD), kv_dt)
        scales = (sds((G, M_C), f32),) * 2 if q8 else ()
        return (q, kv, kv, *scales, kd, vd, dm)
    kv = sds((N_NODES, G, M_C, HD), kv_dt)
    scales = (sds((N_NODES, G, M_C), f32),) * 2 if q8 else ()
    rows = (sds((SLOTS,), i32) if name.startswith("grouped")
            else sds((DEPTH, SLOTS), i32))
    return (q, kv, kv, *scales, rows, sds((N_NODES,), i32), kd, vd, dm)


def _paged_args(q8, pm, sds, chunk=False):
    """Paged-pool dispatchers (paged and packed): a 1024-token capacity per
    node, all in pages of ``pm``; ``chunk`` adds a piggybacked prefill chunk
    (packed only)."""
    bf, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32
    q, kd, vd, dm = _decode_arm(sds)
    ppn = M_C // pm
    n_pages = N_NODES * ppn
    pages = sds((n_pages, G, pm, HD), i8 if q8 else bf)
    scales = (sds((n_pages, G, pm), f32),) * 2 if q8 else ()
    args = (q, pages, pages, *scales, sds((N_NODES, ppn), i32),
            sds((N_NODES,), i32), sds((DEPTH, SLOTS), i32), kd, vd, dm)
    if chunk:
        fresh = sds((2 * pm, G, HD), bf)
        args += (sds((CHUNK_ROWS, G, P_Q, HD), bf), fresh, fresh,
                 sds((), i32), sds((), i32), sds((CHUNK_ROWS,), i32),
                 sds((DEPTH,), i32))
    return args


@pytest.mark.parametrize("name", [
    "fused", "fused_q8", "grouped", "grouped_q8", "tree", "tree_q8"])
def test_dense_context_kernel_compiles_for_v5e(sds, name):
    fn = {
        "fused": ops.bifurcated_decode_attention,
        "fused_q8": ops.bifurcated_decode_attention_q8,
        "grouped": ops.grouped_bifurcated_decode_attention,
        "grouped_q8": ops.grouped_bifurcated_decode_attention_q8,
        "tree": ops.tree_bifurcated_decode_attention,
        "tree_q8": ops.tree_bifurcated_decode_attention_q8,
    }[name]
    assert _compile_has_kernel(fn, *_dense_args(name, sds))


@pytest.mark.parametrize("pm", [64, 128, 256])
@pytest.mark.parametrize("q8", [False, True], ids=["bf16", "int8"])
def test_paged_kernel_compiles_for_v5e(sds, q8, pm):
    fn = (ops.paged_bifurcated_decode_attention_q8 if q8
          else ops.paged_bifurcated_decode_attention)
    assert _compile_has_kernel(fn, *_paged_args(q8, pm, sds))


@pytest.mark.parametrize("chunk", [False, True],
                         ids=["decode_only", "with_chunk"])
@pytest.mark.parametrize("pm", [64, 128, 256])
@pytest.mark.parametrize("q8", [False, True], ids=["bf16", "int8"])
def test_packed_kernel_compiles_for_v5e(sds, q8, pm, chunk):
    fn = (ops.packed_bifurcated_decode_attention_q8 if q8
          else ops.packed_bifurcated_decode_attention)
    assert _compile_has_kernel(fn, *_paged_args(q8, pm, sds, chunk=chunk))
