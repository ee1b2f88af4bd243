"""Pallas TPU kernels: fused bifurcated flash-decode.

The paper's context GEMM (⟨q, K_c⟩, Eq. 3) is the memory-IO hot spot of
shared-prefix batch decoding: K_c is the one tensor whose HBM traffic the
technique eliminates b-fold. Eleven kernels live here:

``fused_bifurcated_decode`` — the deployable single-pass path. One
  ``pallas_call`` over grid ``(g, nb_ctx + 1)``: for each kv group the
  K_c/V_c blocks stream HBM -> VMEM exactly once while all ``b*p*n`` query
  rows ride the MXU's row dimension (batch becomes compute parallelism, not
  memory replication); the FINAL grid step loads the per-sample decode cache,
  folds its logits into the same running fp32 ``(max, sumexp, acc)`` VMEM
  scratch with the decode-slot mask applied in-kernel, and writes the
  NORMALIZED ``(g, rows, hd)`` output directly. Nothing but the output ever
  touches HBM: no ``b*h*m_c`` logits (einsum path) and no fp32
  ``acc/m/l`` partials (two-pass path) are materialized.

``fused_bifurcated_decode_q8`` — the same single-pass structure with an
  INT8 context arm: K_c/V_c blocks stream as int8 plus per-(token, head)
  f32 scale vectors (k_scale carries the logit scale pre-folded), are
  dequantized in-register — scales fold into the logits (K) and the softmax
  weights (V) — and merge into the identical fp32 VMEM running state. The
  dominant remaining HBM term (context KV) halves; no dequantized KV tensor
  ever exists in HBM.

``grouped_fused_bifurcated_decode`` / ``..._q8`` — the multi-prefix FOREST
  twins: the grid gains a prefix-group axis (g, G, nb) and G context
  segments stream through VMEM in turn, each DMA'd from HBM once per kv
  head per step no matter how many decode slots share that prefix. Rows
  not assigned to the current group and ragged per-group context tails are
  masked in-kernel (lane-replicated ``(rows, 128)`` assignment + a
  ``(G, m_c)`` length bias — admission state is DATA, so continuous
  batching never recompiles); the decode arm + normalize fold into the
  last grid step. At G == 1 both reduce bit-identically to the
  single-prefix kernels above.

``tree_fused_bifurcated_decode`` / ``..._q8`` — the hierarchical CASCADE
  twins (Hydragen / CoDec lineage): the segment grid axis runs over the N
  nodes of a prefix TRIE and each row accumulates every node on its
  static-depth ancestor path (a lane-replicated ``(depth, rows, 128)`` path
  table, OR-membership unrolled over the static depth). Each node's K/V is
  DMA'd from HBM once per kv head per step no matter how many paths
  traverse it — the flat forest kernels above are the depth == 1 special
  case and the reduction is bit-identical.

``paged_fused_bifurcated_decode`` / ``..._q8`` — the PAGED substrate's
  general form (core/paged.py): context KV lives in a head-major page pool
  addressed through per-segment block tables, and the dense kernels'
  (segment, block) grid axes collapse into one page-walk axis driven by a
  scalar-prefetched LIVE-page list — fully-FREE segments and pages past
  each segment's live length are never DMA'd (structural early exit, not
  in-register masking). Single-prefix decoding is one segment with
  all-zero paths, the forest is depth == 1, the trie the full path table;
  on the same logical contents the output is bit-identical to the dense
  kernels at ``page_m == block_m``.

``packed_fused_bifurcated_decode`` / ``..._q8`` — the HETEROGENEOUS-STEP
  generalization (PackInfer / CoDec lineage): the scalar-prefetched
  live-page list becomes a WORK-QUEUE of (kind, seg, page/offset)
  descriptors and one grid walks it, processing decode page-reads
  (kind == 0, pool pages) AND chunked suffix-prefill tiles (kind == 1,
  fresh KV the queue positions at an absolute offset with per-row causal
  masking) in the SAME launch — prefill rows join the same fp32
  (max, sumexp, acc) VMEM state as the decode rows, the decode arm folds
  into the final step, and dead capacity is never enqueued so it is
  structurally never streamed. On a decode-only queue every descriptor is
  a pool page and the per-entry op sequence reduces bit-identically to
  the paged kernels. Static ``carry``/``emit_partials`` modes chain
  launches exactly (raw fp32 state in/out) when a queue exceeds one
  launch's grid envelope — the one deliberate exception to the no-spill
  contract, used only for multi-launch spill.

``context_flash_partials`` — the historical two-pass building block (context
  arm only, spills unnormalized partials to HBM for a host-side merge with
  the einsum decode arm). Kept as the ``two_pass=True`` escape hatch in
  ``ops.bifurcated_decode_attention`` and as a merge-correctness oracle.

TPU mapping notes:
  * block_m is MXU/lane aligned (multiple of 128); K_c tail is masked via
    the static m_c closed over by the kernel.
  * per-row stats are kept as (rows, 128) replicated-lane tiles — the
    standard Mosaic idiom for rowwise scalars.
  * rows = b * p * n (samples x queries-per-group x new tokens): for b >= 8
    this saturates the 8x128 MXU sublane tile even when p == 1 (MQA).
  * the decode arm is computed as ONE (rows, b*C_d) GEMM against the
    concatenation of every sample's decode keys, with the cross-sample
    pairs masked via iota — C_d is small, so the b-fold FLOP overhead is
    noise next to the context arm while keeping the whole arm on the MXU.
    The decode tile is (rows, b*C_d); for very large b*C_d the decode arm
    would need its own grid axis (future work, irrelevant at paper scales).
  * during the final (decode) grid step the context block index is pinned to
    the previous block, so Pallas's revisiting rule skips the DMA.
  * Mosaic requires the last two dims of every block to be divisible by
    (8, 128) or to equal the array's. Per-token vectors (int8 scales,
    ragged-tail and chunk biases) are read one row at a time, so the
    wrappers give them a unit second-minor axis (a free reshape) and the
    block spans it: ``(1, 1, pm)`` over ``(n, 1, pm)``.

Validated on CPU in interpret mode against `ref.py` over a shape/dtype sweep
(tests/test_kernels.py, tests/test_fused_decode.py) and compiled with Mosaic
for a described v5e at real widths (tests/test_tpu_compile.py); intended
layout for deployment: K_c stored (g, m_c, hd) ("gmk", the framework
default) so block DMA is contiguous and no per-layer transpose copy is
needed.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_update(s, v, acc_scr, m_scr, l_scr, p_scale=None):
    """One flash block step: fold logits ``s`` (rows, m) and values ``v``
    (m, hd) into the running VMEM (acc, max, sumexp) scratch. Returns the
    updated (acc, l) so a final grid step can normalize without re-reading
    scratch. The single definition keeps the numerically delicate update
    identical across all kernels and both arms.

    ``p_scale`` (1, m): optional per-column multiplier folded into the
    softmax weights BEFORE the value contraction (the quantized arm's
    ``w * s_v`` fold) — the sumexp ``l`` stays unscaled."""
    m_prev = m_scr[:, :1]             # (rows, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)            # (rows, m)
    l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv_in = p if p_scale is None else p * p_scale
    pv = jax.lax.dot_general(
        pv_in.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                  # (rows, hd)
    acc_new = acc_scr[...] * corr + pv
    acc_scr[...] = acc_new
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    return acc_new, l_new


# ---------------------------------------------------------------------------
# Single-pass fused kernel: context stream + decode arm + in-kernel merge
# ---------------------------------------------------------------------------

def _fused_kernel(
    q_ref,      # (1, rows, hd)
    k_ref,      # (1, block_m, hd) — context block
    v_ref,      # (1, block_m, hd)
    kd_ref,     # (1, ld, hd)      — ALL samples' decode keys, group-major
    vd_ref,     # (1, ld, hd)
    bias_ref,   # (1, ld) f32      — decode-slot mask bias (0 / NEG_INF)
    out_ref,    # out: (1, rows, hd) — normalized attention output
    acc_scr,    # scratch (rows, hd) f32
    m_scr,      # scratch (rows, 128) f32
    l_scr,      # scratch (rows, 128) f32
    *,
    scale: float,
    m_c: int,
    block_m: int,
    c_d: int,
    pn: int,
):
    i = pl.program_id(1)
    nb = pl.num_programs(1) - 1   # context blocks; step nb is the decode arm

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                      # (rows, hd)

    @pl.when(i < nb)
    def _context_block():
        k = k_ref[0]                  # (block_m, hd)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (rows, block_m)

        # mask the zero-padded K tail of the last block
        pos = i * block_m + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < m_c, s, NEG_INF)
        _online_update(s, v, acc_scr, m_scr, l_scr)

    @pl.when(i == nb)
    def _decode_arm_and_flush():
        kd = kd_ref[0]                # (ld, hd)
        vd = vd_ref[0]
        s = jax.lax.dot_general(
            q, kd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (rows, ld)
        s = s + bias_ref[...]          # slot validity + ld padding
        # cross-sample mask: row r belongs to sample r // pn and may only
        # attend to decode slots of the same sample (cols j // c_d).
        row_s = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // pn
        col_s = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // c_d
        s = jnp.where(row_s == col_s, s, NEG_INF)

        acc, l_new = _online_update(s, vd, acc_scr, m_scr, l_scr)
        out_ref[0] = (acc / jnp.maximum(l_new, 1e-30)).astype(out_ref.dtype)


def fused_bifurcated_decode(
    q: jnp.ndarray,        # (g, rows, hd)  rows = b * p * n
    k_ctx: jnp.ndarray,    # (g, m_c, hd)
    v_ctx: jnp.ndarray,    # (g, m_c, hd)
    k_dec: jnp.ndarray,    # (g, b * c_d, hd) — group-major flattened decode
    v_dec: jnp.ndarray,    # (g, b * c_d, hd)
    dec_bias: jnp.ndarray, # (1, b * c_d) f32 — 0 for live slots, NEG_INF else
    *,
    scale: float,
    c_d: int,
    pn: int,
    block_m: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Single-pallas_call bifurcated decode: returns normalized (g, rows, hd).

    The only HBM output is the attention result in the query dtype — the
    fp32 (acc, m, l) running state lives and dies in VMEM scratch.
    """
    g, rows, hd = q.shape
    m_c = k_ctx.shape[1]
    block_m = min(block_m, max(128, m_c))
    pad = (-m_c) % block_m
    if pad:
        k_ctx = jnp.pad(k_ctx, ((0, 0), (0, pad), (0, 0)))
        v_ctx = jnp.pad(v_ctx, ((0, 0), (0, pad), (0, 0)))
    nb = k_ctx.shape[1] // block_m

    ld = k_dec.shape[1]
    ld_pad = (-ld) % 128   # lane-align the decode tile
    if ld_pad:
        k_dec = jnp.pad(k_dec, ((0, 0), (0, ld_pad), (0, 0)))
        v_dec = jnp.pad(v_dec, ((0, 0), (0, ld_pad), (0, 0)))
        dec_bias = jnp.pad(dec_bias, ((0, 0), (0, ld_pad)),
                           constant_values=NEG_INF)
    ld_full = ld + ld_pad

    kernel = functools.partial(
        _fused_kernel, scale=scale, m_c=m_c, block_m=block_m, c_d=c_d, pn=pn
    )
    out = pl.pallas_call(
        kernel,
        grid=(g, nb + 1),
        in_specs=[
            pl.BlockSpec((1, rows, hd), lambda gi, i: (gi, 0, 0)),
            # pin the ctx index during the decode step: same block index as
            # the previous iteration => the revisiting rule skips the DMA.
            pl.BlockSpec((1, block_m, hd),
                         lambda gi, i: (gi, jnp.minimum(i, nb - 1), 0)),
            pl.BlockSpec((1, block_m, hd),
                         lambda gi, i: (gi, jnp.minimum(i, nb - 1), 0)),
            pl.BlockSpec((1, ld_full, hd), lambda gi, i: (gi, 0, 0)),
            pl.BlockSpec((1, ld_full, hd), lambda gi, i: (gi, 0, 0)),
            pl.BlockSpec((1, ld_full), lambda gi, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, hd), lambda gi, i: (gi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, rows, hd), q.dtype),
        scratch_shapes=[
            # fp32 VMEM accumulators — never spilled to HBM. Working set per
            # grid step: rows*hd (q) + 2*block_m*hd (ctx kv) + 2*ld*hd
            # (decode kv) + rows*(hd+256) (stats) floats; with rows=256,
            # hd=128, block_m=512, ld=4096 that is ~3.1 MB << 16 MB VMEM.
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_ctx, v_ctx, k_dec, v_dec, dec_bias)
    return out


# ---------------------------------------------------------------------------
# Single-pass fused kernel, int8 context arm (quantized-context decode)
# ---------------------------------------------------------------------------

def _fused_q8_kernel(
    q_ref,      # (1, rows, hd)
    k_ref,      # (1, block_m, hd) int8 — quantized context block
    v_ref,      # (1, block_m, hd) int8
    ks_ref,     # (1, 1, block_m) f32 — per-(token, head) K scales, logit
                #   scale PRE-FOLDED at quantize time (no multiply by `scale`)
    vs_ref,     # (1, 1, block_m) f32 — per-(token, head) V scales
    kd_ref,     # (1, ld, hd) bf16 — ALL samples' decode keys, group-major
    vd_ref,     # (1, ld, hd)
    bias_ref,   # (1, ld) f32      — decode-slot mask bias (0 / NEG_INF)
    out_ref,    # out: (1, rows, hd) — normalized attention output
    acc_scr,    # scratch (rows, hd) f32
    m_scr,      # scratch (rows, 128) f32
    l_scr,      # scratch (rows, 128) f32
    *,
    scale: float,
    m_c: int,
    block_m: int,
    c_d: int,
    pn: int,
):
    """Quantized twin of ``_fused_kernel``: the context K/V blocks arrive as
    int8 + f32 scales and are dequantized IN-REGISTER — the scales fold into
    the logits (K) and the softmax weights (V), so no dequantized KV tensor
    ever exists, in HBM or VMEM. The decode arm and the running fp32
    (max, sumexp, acc) state are identical to the bf16 kernel."""
    i = pl.program_id(1)
    nb = pl.num_programs(1) - 1   # context blocks; step nb is the decode arm

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                      # (rows, hd)

    @pl.when(i < nb)
    def _context_block():
        k = k_ref[0].astype(jnp.float32)   # int8 -> f32, in-register
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                              # (rows, block_m) — raw q·K_q
        s = s * ks_ref[0]              # fold s_k (logit scale pre-folded)

        # mask the zero-padded K tail of the last block
        pos = i * block_m + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < m_c, s, NEG_INF)
        _online_update(s, v, acc_scr, m_scr, l_scr, p_scale=vs_ref[0])

    @pl.when(i == nb)
    def _decode_arm_and_flush():
        kd = kd_ref[0]                # (ld, hd) bf16
        vd = vd_ref[0]
        s = jax.lax.dot_general(
            q, kd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (rows, ld)
        s = s + bias_ref[...]          # slot validity + ld padding
        row_s = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // pn
        col_s = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // c_d
        s = jnp.where(row_s == col_s, s, NEG_INF)

        acc, l_new = _online_update(s, vd, acc_scr, m_scr, l_scr)
        out_ref[0] = (acc / jnp.maximum(l_new, 1e-30)).astype(out_ref.dtype)


def fused_bifurcated_decode_q8(
    q: jnp.ndarray,        # (g, rows, hd)  rows = b * p * n
    k_ctx_q: jnp.ndarray,  # (g, m_c, hd) int8
    v_ctx_q: jnp.ndarray,  # (g, m_c, hd) int8
    k_scale_folded: jnp.ndarray,  # (g, m_c) f32 — MUST carry the logit
    v_scale: jnp.ndarray,         #   scale (hd**-0.5) pre-folded
    k_dec: jnp.ndarray,    # (g, b * c_d, hd) — group-major flattened decode
    v_dec: jnp.ndarray,    # (g, b * c_d, hd)
    dec_bias: jnp.ndarray, # (1, b * c_d) f32 — 0 for live slots, NEG_INF else
    *,
    scale: float,
    c_d: int,
    pn: int,
    block_m: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Single-pallas_call quantized-context bifurcated decode.

    The context arm streams at 1 byte/element (+4 bytes/(token, head) of
    scales) instead of 2 — the dominant remaining HBM term after PR 1 —
    while the output and the fp32 VMEM running state match the bf16 kernel
    bit-for-bit in structure: the only HBM output is the normalized
    attention result in the query dtype.
    """
    k_scale = k_scale_folded
    g, rows, hd = q.shape
    m_c = k_ctx_q.shape[1]
    block_m = min(block_m, max(128, m_c))
    pad = (-m_c) % block_m
    if pad:
        k_ctx_q = jnp.pad(k_ctx_q, ((0, 0), (0, pad), (0, 0)))
        v_ctx_q = jnp.pad(v_ctx_q, ((0, 0), (0, pad), (0, 0)))
        k_scale = jnp.pad(k_scale, ((0, 0), (0, pad)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, pad)))
    nb = k_ctx_q.shape[1] // block_m
    # unit second-minor axes: Mosaic's block rule (module notes)
    k_scale = k_scale[:, None, :]
    v_scale = v_scale[:, None, :]

    ld = k_dec.shape[1]
    ld_pad = (-ld) % 128   # lane-align the decode tile
    if ld_pad:
        k_dec = jnp.pad(k_dec, ((0, 0), (0, ld_pad), (0, 0)))
        v_dec = jnp.pad(v_dec, ((0, 0), (0, ld_pad), (0, 0)))
        dec_bias = jnp.pad(dec_bias, ((0, 0), (0, ld_pad)),
                           constant_values=NEG_INF)
    ld_full = ld + ld_pad

    kernel = functools.partial(
        _fused_q8_kernel, scale=scale, m_c=m_c, block_m=block_m, c_d=c_d,
        pn=pn,
    )
    out = pl.pallas_call(
        kernel,
        grid=(g, nb + 1),
        in_specs=[
            pl.BlockSpec((1, rows, hd), lambda gi, i: (gi, 0, 0)),
            # pin the ctx index during the decode step: same block index as
            # the previous iteration => the revisiting rule skips the DMA.
            pl.BlockSpec((1, block_m, hd),
                         lambda gi, i: (gi, jnp.minimum(i, nb - 1), 0)),
            pl.BlockSpec((1, block_m, hd),
                         lambda gi, i: (gi, jnp.minimum(i, nb - 1), 0)),
            pl.BlockSpec((1, 1, block_m),
                         lambda gi, i: (gi, 0, jnp.minimum(i, nb - 1))),
            pl.BlockSpec((1, 1, block_m),
                         lambda gi, i: (gi, 0, jnp.minimum(i, nb - 1))),
            pl.BlockSpec((1, ld_full, hd), lambda gi, i: (gi, 0, 0)),
            pl.BlockSpec((1, ld_full, hd), lambda gi, i: (gi, 0, 0)),
            pl.BlockSpec((1, ld_full), lambda gi, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, hd), lambda gi, i: (gi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, rows, hd), q.dtype),
        scratch_shapes=[
            # fp32 VMEM accumulators — never spilled to HBM. The int8 ctx
            # blocks halve the per-step DMA footprint vs the bf16 kernel;
            # scale rows add 2*block_m floats (noise).
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_ctx_q, v_ctx_q, k_scale, v_scale, k_dec, v_dec, dec_bias)
    return out


# ---------------------------------------------------------------------------
# Grouped (multi-prefix forest) fused kernels: G context segments per batch
# ---------------------------------------------------------------------------

def _grouped_fused_kernel(
    q_ref,      # (1, rows, hd)
    k_ref,      # (1, 1, block_m, hd) — context block of group gi
    v_ref,      # (1, 1, block_m, hd)
    grp_ref,    # (rows, 128) i32 — lane-replicated row -> group assignment
    cb_ref,     # (1, 1, block_m) f32 — per-group ragged-tail bias
    kd_ref,     # (1, ld, hd)      — ALL slots' decode keys, group-major
    vd_ref,     # (1, ld, hd)
    bias_ref,   # (1, ld) f32      — decode-slot mask bias (0 / NEG_INF)
    out_ref,    # out: (1, rows, hd) — normalized attention output
    acc_scr,    # scratch (rows, hd) f32
    m_scr,      # scratch (rows, 128) f32
    l_scr,      # scratch (rows, 128) f32
    *,
    scale: float,
    c_d: int,
    pn: int,
):
    """Forest twin of ``_fused_kernel``: the grid gains a prefix-group axis
    (g, G, nb). For each kv head the G context segments stream through VMEM
    IN TURN — each group's K_c/V_c blocks are DMA'd from HBM exactly once
    per head regardless of how many decode slots share that prefix — while
    ALL ``rows`` ride the MXU row dimension every step. Rows not assigned
    to the current group are masked to NEG_INF via the lane-replicated
    ``grp_ref`` assignment (so they contribute exp(-inf)=0 to the running
    state, exactly like a masked column); the per-group ragged context tail
    is masked by ``cb_ref``, a (G, m_c_pad) bias sliced per block. The
    decode arm + normalize fold into the LAST grid step, so the running
    fp32 (max, sumexp, acc) state never leaves VMEM."""
    gi = pl.program_id(1)
    i = pl.program_id(2)
    n_groups = pl.num_programs(1)
    nb = pl.num_programs(2)

    @pl.when((gi == 0) & (i == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                      # (rows, hd)
    k = k_ref[0, 0]                   # (block_m, hd)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                          # (rows, block_m)
    # ragged per-group tail (0 / NEG_INF, covers the zero-padded capacity)
    s = s + cb_ref[0]
    # row -> group assignment: only rows decoding THIS prefix contribute
    assigned = grp_ref[:, :1] == gi    # (rows, 1)
    s = jnp.where(assigned, s, NEG_INF)
    _online_update(s, v, acc_scr, m_scr, l_scr)

    @pl.when((gi == n_groups - 1) & (i == nb - 1))
    def _decode_arm_and_flush():
        kd = kd_ref[0]                # (ld, hd)
        vd = vd_ref[0]
        sd = jax.lax.dot_general(
            q, kd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (rows, ld)
        sd = sd + bias_ref[...]        # slot validity + ld padding
        # cross-slot mask: row r belongs to slot r // pn and may only
        # attend to decode slots of the same sample (cols j // c_d).
        row_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 0) // pn
        col_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 1) // c_d
        sd = jnp.where(row_s == col_s, sd, NEG_INF)

        acc, l_new = _online_update(sd, vd, acc_scr, m_scr, l_scr)
        out_ref[0] = (acc / jnp.maximum(l_new, 1e-30)).astype(out_ref.dtype)


def grouped_fused_bifurcated_decode(
    q: jnp.ndarray,         # (g, rows, hd)  rows = b * p * n
    k_ctx: jnp.ndarray,     # (G, g, m_c, hd)
    v_ctx: jnp.ndarray,     # (G, g, m_c, hd)
    row_group: jnp.ndarray, # (rows, 128) i32 lane-replicated row -> group
    ctx_bias: jnp.ndarray,  # (G, m_c) f32 — 0 within ctx_lens[G], NEG_INF past
    k_dec: jnp.ndarray,     # (g, b * c_d, hd) — group-major flattened decode
    v_dec: jnp.ndarray,     # (g, b * c_d, hd)
    dec_bias: jnp.ndarray,  # (1, b * c_d) f32 — 0 for live slots, NEG_INF else
    *,
    scale: float,
    c_d: int,
    pn: int,
    block_m: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Single-pallas_call multi-prefix decode: returns normalized (g, rows, hd).

    HBM traffic per layer-step: each of the G context segments once
    (sum_G m_c), the b*c_d decode slots once, q and the output — the same
    no-spill structure as ``fused_bifurcated_decode``, which this reduces to
    exactly (token-identically) at G == 1.
    """
    n_groups, g, m_c, hd = k_ctx.shape
    rows = q.shape[1]
    block_m = min(block_m, max(128, m_c))
    pad = (-m_c) % block_m
    if pad:
        k_ctx = jnp.pad(k_ctx, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_ctx = jnp.pad(v_ctx, ((0, 0), (0, 0), (0, pad), (0, 0)))
        ctx_bias = jnp.pad(ctx_bias, ((0, 0), (0, pad)),
                           constant_values=NEG_INF)
    nb = k_ctx.shape[2] // block_m
    # unit second-minor axes: Mosaic's block rule (module notes)
    ctx_bias = ctx_bias[:, None, :]

    ld = k_dec.shape[1]
    ld_pad = (-ld) % 128   # lane-align the decode tile
    if ld_pad:
        k_dec = jnp.pad(k_dec, ((0, 0), (0, ld_pad), (0, 0)))
        v_dec = jnp.pad(v_dec, ((0, 0), (0, ld_pad), (0, 0)))
        dec_bias = jnp.pad(dec_bias, ((0, 0), (0, ld_pad)),
                           constant_values=NEG_INF)
    ld_full = ld + ld_pad

    kernel = functools.partial(
        _grouped_fused_kernel, scale=scale, c_d=c_d, pn=pn
    )
    out = pl.pallas_call(
        kernel,
        grid=(g, n_groups, nb),
        in_specs=[
            pl.BlockSpec((1, rows, hd), lambda gk, gi, i: (gk, 0, 0)),
            pl.BlockSpec((1, 1, block_m, hd),
                         lambda gk, gi, i: (gi, gk, i, 0)),
            pl.BlockSpec((1, 1, block_m, hd),
                         lambda gk, gi, i: (gi, gk, i, 0)),
            pl.BlockSpec((rows, 128), lambda gk, gi, i: (0, 0)),
            pl.BlockSpec((1, 1, block_m), lambda gk, gi, i: (gi, 0, i)),
            pl.BlockSpec((1, ld_full, hd), lambda gk, gi, i: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full, hd), lambda gk, gi, i: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full), lambda gk, gi, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, hd), lambda gk, gi, i: (gk, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, rows, hd), q.dtype),
        scratch_shapes=[
            # fp32 VMEM accumulators — never spilled to HBM; same working
            # set as the single-prefix kernel (the G axis adds grid steps,
            # not VMEM residency).
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_ctx, v_ctx, row_group, ctx_bias, k_dec, v_dec, dec_bias)
    return out


def _grouped_fused_q8_kernel(
    q_ref,      # (1, rows, hd)
    k_ref,      # (1, 1, block_m, hd) int8 — quantized context block
    v_ref,      # (1, 1, block_m, hd) int8
    ks_ref,     # (1, 1, 1, block_m) f32 — per-(token, head) K scales,
                #   logit scale PRE-FOLDED at quantize time
    vs_ref,     # (1, 1, 1, block_m) f32
    grp_ref,    # (rows, 128) i32 — lane-replicated row -> group assignment
    cb_ref,     # (1, 1, block_m) f32 — per-group ragged-tail bias
    kd_ref,     # (1, ld, hd) bf16 — ALL slots' decode keys, group-major
    vd_ref,     # (1, ld, hd)
    bias_ref,   # (1, ld) f32      — decode-slot mask bias (0 / NEG_INF)
    out_ref,    # out: (1, rows, hd)
    acc_scr,    # scratch (rows, hd) f32
    m_scr,      # scratch (rows, 128) f32
    l_scr,      # scratch (rows, 128) f32
    *,
    scale: float,
    c_d: int,
    pn: int,
):
    """Quantized twin of ``_grouped_fused_kernel``: int8 context segments +
    per-(token, head) scales dequantized in-register, identical running
    fp32 VMEM state and in-kernel decode-arm merge."""
    gi = pl.program_id(1)
    i = pl.program_id(2)
    n_groups = pl.num_programs(1)
    nb = pl.num_programs(2)

    @pl.when((gi == 0) & (i == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                      # (rows, hd)
    k = k_ref[0, 0].astype(jnp.float32)   # int8 -> f32, in-register
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                  # (rows, block_m) — raw q·K_q
    s = s * ks_ref[0, 0]               # fold s_k (logit scale pre-folded)
    s = s + cb_ref[0]                  # ragged per-group tail
    assigned = grp_ref[:, :1] == gi    # (rows, 1)
    s = jnp.where(assigned, s, NEG_INF)
    _online_update(s, v, acc_scr, m_scr, l_scr, p_scale=vs_ref[0, 0])

    @pl.when((gi == n_groups - 1) & (i == nb - 1))
    def _decode_arm_and_flush():
        kd = kd_ref[0]                # (ld, hd) bf16
        vd = vd_ref[0]
        sd = jax.lax.dot_general(
            q, kd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (rows, ld)
        sd = sd + bias_ref[...]
        row_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 0) // pn
        col_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 1) // c_d
        sd = jnp.where(row_s == col_s, sd, NEG_INF)

        acc, l_new = _online_update(sd, vd, acc_scr, m_scr, l_scr)
        out_ref[0] = (acc / jnp.maximum(l_new, 1e-30)).astype(out_ref.dtype)


def grouped_fused_bifurcated_decode_q8(
    q: jnp.ndarray,         # (g, rows, hd)  rows = b * p * n
    k_ctx_q: jnp.ndarray,   # (G, g, m_c, hd) int8
    v_ctx_q: jnp.ndarray,   # (G, g, m_c, hd) int8
    k_scale_folded: jnp.ndarray,  # (G, g, m_c) f32 — logit scale pre-folded
    v_scale: jnp.ndarray,         # (G, g, m_c) f32
    row_group: jnp.ndarray, # (rows, 128) i32 lane-replicated row -> group
    ctx_bias: jnp.ndarray,  # (G, m_c) f32 — 0 within ctx_lens[G], NEG_INF past
    k_dec: jnp.ndarray,     # (g, b * c_d, hd) bf16
    v_dec: jnp.ndarray,     # (g, b * c_d, hd)
    dec_bias: jnp.ndarray,  # (1, b * c_d) f32
    *,
    scale: float,
    c_d: int,
    pn: int,
    block_m: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Single-pallas_call quantized multi-prefix decode: every context
    segment streams as int8 + f32 scale vectors (half the dominant HBM
    term), no dequantized KV tensor or fp32 partial ever exists in HBM."""
    k_scale = k_scale_folded
    n_groups, g, m_c, hd = k_ctx_q.shape
    rows = q.shape[1]
    block_m = min(block_m, max(128, m_c))
    pad = (-m_c) % block_m
    if pad:
        k_ctx_q = jnp.pad(k_ctx_q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_ctx_q = jnp.pad(v_ctx_q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_scale = jnp.pad(k_scale, ((0, 0), (0, 0), (0, pad)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, 0), (0, pad)))
        ctx_bias = jnp.pad(ctx_bias, ((0, 0), (0, pad)),
                           constant_values=NEG_INF)
    nb = k_ctx_q.shape[2] // block_m
    # unit second-minor axes: Mosaic's block rule (module notes)
    ctx_bias = ctx_bias[:, None, :]
    k_scale = k_scale[:, :, None, :]
    v_scale = v_scale[:, :, None, :]

    ld = k_dec.shape[1]
    ld_pad = (-ld) % 128   # lane-align the decode tile
    if ld_pad:
        k_dec = jnp.pad(k_dec, ((0, 0), (0, ld_pad), (0, 0)))
        v_dec = jnp.pad(v_dec, ((0, 0), (0, ld_pad), (0, 0)))
        dec_bias = jnp.pad(dec_bias, ((0, 0), (0, ld_pad)),
                           constant_values=NEG_INF)
    ld_full = ld + ld_pad

    kernel = functools.partial(
        _grouped_fused_q8_kernel, scale=scale, c_d=c_d, pn=pn
    )
    out = pl.pallas_call(
        kernel,
        grid=(g, n_groups, nb),
        in_specs=[
            pl.BlockSpec((1, rows, hd), lambda gk, gi, i: (gk, 0, 0)),
            pl.BlockSpec((1, 1, block_m, hd),
                         lambda gk, gi, i: (gi, gk, i, 0)),
            pl.BlockSpec((1, 1, block_m, hd),
                         lambda gk, gi, i: (gi, gk, i, 0)),
            pl.BlockSpec((1, 1, 1, block_m),
                         lambda gk, gi, i: (gi, gk, 0, i)),
            pl.BlockSpec((1, 1, 1, block_m),
                         lambda gk, gi, i: (gi, gk, 0, i)),
            pl.BlockSpec((rows, 128), lambda gk, gi, i: (0, 0)),
            pl.BlockSpec((1, 1, block_m), lambda gk, gi, i: (gi, 0, i)),
            pl.BlockSpec((1, ld_full, hd), lambda gk, gi, i: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full, hd), lambda gk, gi, i: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full), lambda gk, gi, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, hd), lambda gk, gi, i: (gk, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, rows, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_ctx_q, v_ctx_q, k_scale, v_scale, row_group, ctx_bias,
      k_dec, v_dec, dec_bias)
    return out


# ---------------------------------------------------------------------------
# Tree (hierarchical prefix-trie / cascade) fused kernels: N trie nodes,
# static-depth slot -> node paths
# ---------------------------------------------------------------------------

def _tree_fused_kernel(
    q_ref,      # (1, rows, hd)
    k_ref,      # (1, 1, block_m, hd) — context block of trie node ni
    v_ref,      # (1, 1, block_m, hd)
    path_ref,   # (depth, rows, 128) i32 — lane-replicated row -> node id per
                #   trie level (-1 = level unused by that row)
    cb_ref,     # (1, 1, block_m) f32 — per-node ragged-tail bias
    kd_ref,     # (1, ld, hd)      — ALL slots' decode keys, group-major
    vd_ref,     # (1, ld, hd)
    bias_ref,   # (1, ld) f32      — decode-slot mask bias (0 / NEG_INF)
    out_ref,    # out: (1, rows, hd) — normalized attention output
    acc_scr,    # scratch (rows, hd) f32
    m_scr,      # scratch (rows, 128) f32
    l_scr,      # scratch (rows, 128) f32
    *,
    scale: float,
    c_d: int,
    pn: int,
    depth: int,
):
    """Cascade (prefix-trie) generalization of ``_grouped_fused_kernel``:
    the grid's segment axis runs over the N trie NODES, and a row joins the
    accumulation of every node on its ancestor PATH instead of exactly one
    group. Membership is the OR over the static ``depth`` path levels —
    at depth == 1 the emitted op sequence is identical to the forest kernel
    (one comparison), which is what makes the L=2 reduction bit-exact.

    Softmax exactness across levels needs no special handling: a masked
    node's block contributes ``exp(NEG_INF - m) == 0`` once the row has seen
    any real column, and the running (max, sumexp, acc) state accumulated
    BEFORE the row's first real column is wiped by the ``corr = exp(m_prev -
    m_new) == 0`` rescale the moment one arrives — so streaming the nodes
    in arbitrary order is exact, and each node's K/V is DMA'd from HBM once
    per kv head per step no matter how many paths (rows) traverse it."""
    ni = pl.program_id(1)
    i = pl.program_id(2)
    n_nodes = pl.num_programs(1)
    nb = pl.num_programs(2)

    @pl.when((ni == 0) & (i == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                      # (rows, hd)
    k = k_ref[0, 0]                   # (block_m, hd)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                          # (rows, block_m)
    # ragged per-node tail (0 / NEG_INF, covers the zero-padded capacity)
    s = s + cb_ref[0]
    # path membership: a row contributes iff node ni sits on its path at
    # ANY level (unrolled over the static depth; -1 never matches).
    assigned = path_ref[0][:, :1] == ni   # (rows, 1)
    for lvl in range(1, depth):
        assigned |= path_ref[lvl][:, :1] == ni
    s = jnp.where(assigned, s, NEG_INF)
    _online_update(s, v, acc_scr, m_scr, l_scr)

    @pl.when((ni == n_nodes - 1) & (i == nb - 1))
    def _decode_arm_and_flush():
        kd = kd_ref[0]                # (ld, hd)
        vd = vd_ref[0]
        sd = jax.lax.dot_general(
            q, kd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (rows, ld)
        sd = sd + bias_ref[...]        # slot validity + ld padding
        # cross-slot mask: row r belongs to slot r // pn and may only
        # attend to decode slots of the same sample (cols j // c_d).
        row_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 0) // pn
        col_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 1) // c_d
        sd = jnp.where(row_s == col_s, sd, NEG_INF)

        acc, l_new = _online_update(sd, vd, acc_scr, m_scr, l_scr)
        out_ref[0] = (acc / jnp.maximum(l_new, 1e-30)).astype(out_ref.dtype)


def tree_fused_bifurcated_decode(
    q: jnp.ndarray,         # (g, rows, hd)  rows = b * p * n
    k_ctx: jnp.ndarray,     # (N, g, m_c, hd) — trie-node KV segments
    v_ctx: jnp.ndarray,     # (N, g, m_c, hd)
    path_rows: jnp.ndarray, # (depth, rows, 128) i32 lane-replicated
                            #   row -> node id per level (-1 = unused)
    ctx_bias: jnp.ndarray,  # (N, m_c) f32 — 0 within node_lens[N], NEG_INF past
    k_dec: jnp.ndarray,     # (g, b * c_d, hd) — group-major flattened decode
    v_dec: jnp.ndarray,     # (g, b * c_d, hd)
    dec_bias: jnp.ndarray,  # (1, b * c_d) f32 — 0 for live slots, NEG_INF else
    *,
    scale: float,
    c_d: int,
    pn: int,
    block_m: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Single-pallas_call hierarchical (L-level cascade) decode: returns the
    normalized (g, rows, hd) attention output.

    HBM traffic per layer-step: each of the N trie nodes' K/V segments once
    (sum_N m_c) — NOT once per path that traverses them — plus the b*c_d
    decode slots, q, the (depth, rows, 128) path table, and the output; the
    same no-spill structure as ``grouped_fused_bifurcated_decode``, which
    this reduces to exactly (bit-identically) at depth == 1, and hence to
    ``fused_bifurcated_decode`` at depth == 1 with a single node.
    """
    depth = path_rows.shape[0]
    n_nodes, g, m_c, hd = k_ctx.shape
    rows = q.shape[1]
    block_m = min(block_m, max(128, m_c))
    pad = (-m_c) % block_m
    if pad:
        k_ctx = jnp.pad(k_ctx, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_ctx = jnp.pad(v_ctx, ((0, 0), (0, 0), (0, pad), (0, 0)))
        ctx_bias = jnp.pad(ctx_bias, ((0, 0), (0, pad)),
                           constant_values=NEG_INF)
    nb = k_ctx.shape[2] // block_m
    # unit second-minor axes: Mosaic's block rule (module notes)
    ctx_bias = ctx_bias[:, None, :]

    ld = k_dec.shape[1]
    ld_pad = (-ld) % 128   # lane-align the decode tile
    if ld_pad:
        k_dec = jnp.pad(k_dec, ((0, 0), (0, ld_pad), (0, 0)))
        v_dec = jnp.pad(v_dec, ((0, 0), (0, ld_pad), (0, 0)))
        dec_bias = jnp.pad(dec_bias, ((0, 0), (0, ld_pad)),
                           constant_values=NEG_INF)
    ld_full = ld + ld_pad

    kernel = functools.partial(
        _tree_fused_kernel, scale=scale, c_d=c_d, pn=pn, depth=depth
    )
    out = pl.pallas_call(
        kernel,
        grid=(g, n_nodes, nb),
        in_specs=[
            pl.BlockSpec((1, rows, hd), lambda gk, ni, i: (gk, 0, 0)),
            pl.BlockSpec((1, 1, block_m, hd),
                         lambda gk, ni, i: (ni, gk, i, 0)),
            pl.BlockSpec((1, 1, block_m, hd),
                         lambda gk, ni, i: (ni, gk, i, 0)),
            pl.BlockSpec((depth, rows, 128), lambda gk, ni, i: (0, 0, 0)),
            pl.BlockSpec((1, 1, block_m), lambda gk, ni, i: (ni, 0, i)),
            pl.BlockSpec((1, ld_full, hd), lambda gk, ni, i: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full, hd), lambda gk, ni, i: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full), lambda gk, ni, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, hd), lambda gk, ni, i: (gk, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, rows, hd), q.dtype),
        scratch_shapes=[
            # fp32 VMEM accumulators — never spilled to HBM; the node axis
            # adds grid steps, not VMEM residency (same working set as the
            # forest kernel plus the small static path table).
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_ctx, v_ctx, path_rows, ctx_bias, k_dec, v_dec, dec_bias)
    return out


def _tree_fused_q8_kernel(
    q_ref,      # (1, rows, hd)
    k_ref,      # (1, 1, block_m, hd) int8 — quantized node context block
    v_ref,      # (1, 1, block_m, hd) int8
    ks_ref,     # (1, 1, 1, block_m) f32 — per-(token, head) K scales,
                #   logit scale PRE-FOLDED at quantize time
    vs_ref,     # (1, 1, 1, block_m) f32
    path_ref,   # (depth, rows, 128) i32 — lane-replicated row -> node id
    cb_ref,     # (1, 1, block_m) f32 — per-node ragged-tail bias
    kd_ref,     # (1, ld, hd) bf16 — ALL slots' decode keys, group-major
    vd_ref,     # (1, ld, hd)
    bias_ref,   # (1, ld) f32      — decode-slot mask bias (0 / NEG_INF)
    out_ref,    # out: (1, rows, hd)
    acc_scr,    # scratch (rows, hd) f32
    m_scr,      # scratch (rows, 128) f32
    l_scr,      # scratch (rows, 128) f32
    *,
    scale: float,
    c_d: int,
    pn: int,
    depth: int,
):
    """Quantized twin of ``_tree_fused_kernel``: int8 trie-node segments +
    per-(token, head) scales dequantized in-register, identical running
    fp32 VMEM state and in-kernel decode-arm merge."""
    ni = pl.program_id(1)
    i = pl.program_id(2)
    n_nodes = pl.num_programs(1)
    nb = pl.num_programs(2)

    @pl.when((ni == 0) & (i == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                      # (rows, hd)
    k = k_ref[0, 0].astype(jnp.float32)   # int8 -> f32, in-register
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                  # (rows, block_m) — raw q·K_q
    s = s * ks_ref[0, 0]               # fold s_k (logit scale pre-folded)
    s = s + cb_ref[0]                  # ragged per-node tail
    assigned = path_ref[0][:, :1] == ni   # (rows, 1)
    for lvl in range(1, depth):
        assigned |= path_ref[lvl][:, :1] == ni
    s = jnp.where(assigned, s, NEG_INF)
    _online_update(s, v, acc_scr, m_scr, l_scr, p_scale=vs_ref[0, 0])

    @pl.when((ni == n_nodes - 1) & (i == nb - 1))
    def _decode_arm_and_flush():
        kd = kd_ref[0]                # (ld, hd) bf16
        vd = vd_ref[0]
        sd = jax.lax.dot_general(
            q, kd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (rows, ld)
        sd = sd + bias_ref[...]
        row_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 0) // pn
        col_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 1) // c_d
        sd = jnp.where(row_s == col_s, sd, NEG_INF)

        acc, l_new = _online_update(sd, vd, acc_scr, m_scr, l_scr)
        out_ref[0] = (acc / jnp.maximum(l_new, 1e-30)).astype(out_ref.dtype)


def tree_fused_bifurcated_decode_q8(
    q: jnp.ndarray,         # (g, rows, hd)  rows = b * p * n
    k_ctx_q: jnp.ndarray,   # (N, g, m_c, hd) int8 — trie-node KV segments
    v_ctx_q: jnp.ndarray,   # (N, g, m_c, hd) int8
    k_scale_folded: jnp.ndarray,  # (N, g, m_c) f32 — logit scale pre-folded
    v_scale: jnp.ndarray,         # (N, g, m_c) f32
    path_rows: jnp.ndarray, # (depth, rows, 128) i32 lane-replicated
    ctx_bias: jnp.ndarray,  # (N, m_c) f32 — 0 within node_lens[N], NEG_INF past
    k_dec: jnp.ndarray,     # (g, b * c_d, hd) bf16
    v_dec: jnp.ndarray,     # (g, b * c_d, hd)
    dec_bias: jnp.ndarray,  # (1, b * c_d) f32
    *,
    scale: float,
    c_d: int,
    pn: int,
    block_m: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """Single-pallas_call quantized hierarchical decode: every trie node
    streams as int8 + f32 scale vectors (half the dominant HBM term), no
    dequantized KV tensor or fp32 partial ever exists in HBM. Reduces
    bit-identically to ``grouped_fused_bifurcated_decode_q8`` at depth == 1.
    """
    k_scale = k_scale_folded
    depth = path_rows.shape[0]
    n_nodes, g, m_c, hd = k_ctx_q.shape
    rows = q.shape[1]
    block_m = min(block_m, max(128, m_c))
    pad = (-m_c) % block_m
    if pad:
        k_ctx_q = jnp.pad(k_ctx_q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_ctx_q = jnp.pad(v_ctx_q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_scale = jnp.pad(k_scale, ((0, 0), (0, 0), (0, pad)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, 0), (0, pad)))
        ctx_bias = jnp.pad(ctx_bias, ((0, 0), (0, pad)),
                           constant_values=NEG_INF)
    nb = k_ctx_q.shape[2] // block_m
    # unit second-minor axes: Mosaic's block rule (module notes)
    ctx_bias = ctx_bias[:, None, :]
    k_scale = k_scale[:, :, None, :]
    v_scale = v_scale[:, :, None, :]

    ld = k_dec.shape[1]
    ld_pad = (-ld) % 128   # lane-align the decode tile
    if ld_pad:
        k_dec = jnp.pad(k_dec, ((0, 0), (0, ld_pad), (0, 0)))
        v_dec = jnp.pad(v_dec, ((0, 0), (0, ld_pad), (0, 0)))
        dec_bias = jnp.pad(dec_bias, ((0, 0), (0, ld_pad)),
                           constant_values=NEG_INF)
    ld_full = ld + ld_pad

    kernel = functools.partial(
        _tree_fused_q8_kernel, scale=scale, c_d=c_d, pn=pn, depth=depth
    )
    out = pl.pallas_call(
        kernel,
        grid=(g, n_nodes, nb),
        in_specs=[
            pl.BlockSpec((1, rows, hd), lambda gk, ni, i: (gk, 0, 0)),
            pl.BlockSpec((1, 1, block_m, hd),
                         lambda gk, ni, i: (ni, gk, i, 0)),
            pl.BlockSpec((1, 1, block_m, hd),
                         lambda gk, ni, i: (ni, gk, i, 0)),
            pl.BlockSpec((1, 1, 1, block_m),
                         lambda gk, ni, i: (ni, gk, 0, i)),
            pl.BlockSpec((1, 1, 1, block_m),
                         lambda gk, ni, i: (ni, gk, 0, i)),
            pl.BlockSpec((depth, rows, 128), lambda gk, ni, i: (0, 0, 0)),
            pl.BlockSpec((1, 1, block_m), lambda gk, ni, i: (ni, 0, i)),
            pl.BlockSpec((1, ld_full, hd), lambda gk, ni, i: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full, hd), lambda gk, ni, i: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full), lambda gk, ni, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, hd), lambda gk, ni, i: (gk, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, rows, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_ctx_q, v_ctx_q, k_scale, v_scale, path_rows, ctx_bias,
      k_dec, v_dec, dec_bias)
    return out


# ---------------------------------------------------------------------------
# Paged fused kernels: page-pool storage, DMA-eliding page-walk grid
# ---------------------------------------------------------------------------

def _paged_fused_kernel(
    # scalar-prefetch refs (SMEM, available to the index maps too):
    pid_ref,    # (max_pages,) i32 — page-pool index of list position i;
                #   entries past n_live REPEAT the last live page so the
                #   revisiting rule elides their DMA entirely
    pseg_ref,   # (max_pages,) i32 — segment id owning the page at pos i
    nlive_ref,  # (1,) i32 — number of live pages (page-walk early exit)
    # tensor operands:
    q_ref,      # (1, rows, hd)
    k_ref,      # (1, 1, pm, hd) — ONE page of the pool (block (page, gk))
    v_ref,      # (1, 1, pm, hd)
    path_ref,   # (depth, rows, 128) i32 — lane-replicated row -> segment id
                #   per trie level (-1 = level unused by that row)
    cb_ref,     # (1, 1, pm) f32 — per-list-position ragged-tail bias
    kd_ref,     # (1, ld, hd)      — ALL slots' decode keys, group-major
    vd_ref,     # (1, ld, hd)
    bias_ref,   # (1, ld) f32      — decode-slot mask bias (0 / NEG_INF)
    out_ref,    # out: (1, rows, hd) — normalized attention output
    acc_scr,    # scratch (rows, hd) f32
    m_scr,      # scratch (rows, 128) f32
    l_scr,      # scratch (rows, 128) f32
    *,
    scale: float,
    c_d: int,
    pn: int,
    depth: int,
):
    """Paged generalization of ``_tree_fused_kernel``: the segment×block
    grid collapses into ONE page-walk axis driven by a scalar-prefetched
    live-page list. Grid step i loads pool page ``pid_ref[i]`` — the index
    map reads the prefetched list, so which HBM bytes move is runtime DATA
    — and the per-block op sequence (scale, ragged-tail bias, path-
    membership mask, online update) is IDENTICAL to the dense tree kernel,
    which is what makes fully-populated pages bit-exact against it.

    DMA elision is structural, not masked: list entries past ``n_live``
    repeat the last live page (same block index ⇒ the revisiting rule skips
    the copy) and compute is gated on ``i < n_live`` — fully-FREE segments
    and pages past each segment's live length simply never appear in the
    list. Exactness of skipping them is the same argument as the tree
    kernel's node skipping: a skipped block would have contributed
    exp(NEG_INF − m) == 0 columns (or pre-first-column garbage wiped by the
    ``corr == 0`` rescale), so the running (max, sumexp, acc) state is
    bit-identical with or without it."""
    i = pl.program_id(1)
    n_ctx = pl.num_programs(1) - 1   # page-walk steps; last = decode arm

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                      # (rows, hd)

    @pl.when((i < n_ctx) & (i < nlive_ref[0]))
    def _context_page():
        k = k_ref[0, 0]               # (pm, hd)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (rows, pm)
        # ragged per-segment tail (0 / NEG_INF, covers page-pad positions)
        s = s + cb_ref[0]
        # path membership against the segment OWNING this page (unrolled
        # over the static depth; -1 never matches) — same mask op sequence
        # as the dense tree kernel.
        seg = pseg_ref[i]
        assigned = path_ref[0][:, :1] == seg   # (rows, 1)
        for lvl in range(1, depth):
            assigned |= path_ref[lvl][:, :1] == seg
        s = jnp.where(assigned, s, NEG_INF)
        _online_update(s, v, acc_scr, m_scr, l_scr)

    @pl.when(i == n_ctx)
    def _decode_arm_and_flush():
        kd = kd_ref[0]                # (ld, hd)
        vd = vd_ref[0]
        sd = jax.lax.dot_general(
            q, kd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (rows, ld)
        sd = sd + bias_ref[...]        # slot validity + ld padding
        row_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 0) // pn
        col_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 1) // c_d
        sd = jnp.where(row_s == col_s, sd, NEG_INF)

        acc, l_new = _online_update(sd, vd, acc_scr, m_scr, l_scr)
        out_ref[0] = (acc / jnp.maximum(l_new, 1e-30)).astype(out_ref.dtype)


def paged_fused_bifurcated_decode(
    q: jnp.ndarray,          # (g, rows, hd)  rows = b * p * n
    k_pages: jnp.ndarray,    # (P, g, pm, hd) — head-major page pool
    v_pages: jnp.ndarray,    # (P, g, pm, hd)
    page_ids: jnp.ndarray,   # (max_pages,) i32 — live pages first, tail
                             #   repeating the last live page
    page_segs: jnp.ndarray,  # (max_pages,) i32 — owning segment per entry
    n_live: jnp.ndarray,     # (1,) i32 — live page count
    path_rows: jnp.ndarray,  # (depth, rows, 128) i32 lane-replicated
                             #   row -> segment id per level (-1 = unused)
    page_bias: jnp.ndarray,  # (max_pages, pm) f32 — per-entry ragged bias
    k_dec: jnp.ndarray,      # (g, b * c_d, hd) — group-major flattened
    v_dec: jnp.ndarray,      # (g, b * c_d, hd)
    dec_bias: jnp.ndarray,   # (1, b * c_d) f32
    *,
    scale: float,
    c_d: int,
    pn: int,
    interpret: bool = True,
) -> jnp.ndarray:
    """Single-pallas_call PAGED bifurcated decode: returns the normalized
    (g, rows, hd) attention output.

    HBM traffic per layer-step: the ``n_live`` live pool pages once per kv
    head (pm tokens each — page-rounded LIVE length, not padded capacity),
    the b*c_d decode slots, q, the page list/bias, and the output. The
    page walk is driven by scalar-prefetched runtime data, so which pages
    stream changes per step with ZERO recompiles; grid length is the
    static page-table envelope (free steps revisit the last live page —
    no DMA — and skip compute). Same no-spill structure as the dense
    kernels; bit-identical to ``tree_fused_bifurcated_decode`` on the same
    logical contents when ``pm`` equals its ``block_m``.
    """
    depth = path_rows.shape[0]
    g, rows, hd = q.shape
    pm = k_pages.shape[2]
    max_pages = page_ids.shape[0]
    # unit second-minor axes: Mosaic's block rule (module notes)
    page_bias = page_bias[:, None, :]

    ld = k_dec.shape[1]
    ld_pad = (-ld) % 128   # lane-align the decode tile
    if ld_pad:
        k_dec = jnp.pad(k_dec, ((0, 0), (0, ld_pad), (0, 0)))
        v_dec = jnp.pad(v_dec, ((0, 0), (0, ld_pad), (0, 0)))
        dec_bias = jnp.pad(dec_bias, ((0, 0), (0, ld_pad)),
                           constant_values=NEG_INF)
    ld_full = ld + ld_pad

    kernel = functools.partial(
        _paged_fused_kernel, scale=scale, c_d=c_d, pn=pn, depth=depth
    )
    last = max_pages - 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(g, max_pages + 1),
        in_specs=[
            pl.BlockSpec((1, rows, hd),
                         lambda gk, i, pid, seg, nl: (gk, 0, 0)),
            # the page walk: block index = prefetched pool id. During the
            # decode step (and past n_live) the index pins to the previous
            # entry, so the revisiting rule skips the DMA.
            pl.BlockSpec((1, 1, pm, hd),
                         lambda gk, i, pid, seg, nl:
                         (pid[jnp.minimum(i, last)], gk, 0, 0)),
            pl.BlockSpec((1, 1, pm, hd),
                         lambda gk, i, pid, seg, nl:
                         (pid[jnp.minimum(i, last)], gk, 0, 0)),
            pl.BlockSpec((depth, rows, 128),
                         lambda gk, i, pid, seg, nl: (0, 0, 0)),
            pl.BlockSpec((1, 1, pm),
                         lambda gk, i, pid, seg, nl:
                         (jnp.minimum(i, last), 0, 0)),
            pl.BlockSpec((1, ld_full, hd),
                         lambda gk, i, pid, seg, nl: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full, hd),
                         lambda gk, i, pid, seg, nl: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full),
                         lambda gk, i, pid, seg, nl: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, hd),
                               lambda gk, i, pid, seg, nl: (gk, 0, 0)),
        scratch_shapes=[
            # fp32 VMEM accumulators — never spilled to HBM; the page walk
            # adds grid steps, not VMEM residency (working set = one page
            # of K/V + the usual q/decode/stat tiles).
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, rows, hd), q.dtype),
        interpret=interpret,
    )(page_ids, page_segs, n_live,
      q, k_pages, v_pages, path_rows, page_bias, k_dec, v_dec, dec_bias)
    return out


def _paged_fused_q8_kernel(
    pid_ref,    # (max_pages,) i32 — scalar-prefetched page list
    pseg_ref,   # (max_pages,) i32
    nlive_ref,  # (1,) i32
    q_ref,      # (1, rows, hd)
    k_ref,      # (1, 1, pm, hd) int8 — quantized pool page
    v_ref,      # (1, 1, pm, hd) int8
    ks_ref,     # (1, 1, 1, pm) f32 — per-(token, head) K scales, logit
                #   scale PRE-FOLDED at quantize time
    vs_ref,     # (1, 1, 1, pm) f32
    path_ref,   # (depth, rows, 128) i32
    cb_ref,     # (1, 1, pm) f32
    kd_ref,     # (1, ld, hd) bf16
    vd_ref,     # (1, ld, hd)
    bias_ref,   # (1, ld) f32
    out_ref,    # out: (1, rows, hd)
    acc_scr,    # scratch (rows, hd) f32
    m_scr,      # scratch (rows, 128) f32
    l_scr,      # scratch (rows, 128) f32
    *,
    scale: float,
    c_d: int,
    pn: int,
    depth: int,
):
    """Quantized twin of ``_paged_fused_kernel``: int8 pool pages + f32
    scale pages walked by the same prefetched list, dequantized in-register
    — identical running fp32 VMEM state and in-kernel decode-arm merge,
    bit-identical per-page op sequence to ``_tree_fused_q8_kernel``."""
    i = pl.program_id(1)
    n_ctx = pl.num_programs(1) - 1

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                      # (rows, hd)

    @pl.when((i < n_ctx) & (i < nlive_ref[0]))
    def _context_page():
        k = k_ref[0, 0].astype(jnp.float32)   # int8 -> f32, in-register
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                              # (rows, pm) — raw q·K_q
        s = s * ks_ref[0, 0]           # fold s_k (logit scale pre-folded)
        s = s + cb_ref[0]              # ragged per-segment tail
        seg = pseg_ref[i]
        assigned = path_ref[0][:, :1] == seg   # (rows, 1)
        for lvl in range(1, depth):
            assigned |= path_ref[lvl][:, :1] == seg
        s = jnp.where(assigned, s, NEG_INF)
        _online_update(s, v, acc_scr, m_scr, l_scr, p_scale=vs_ref[0, 0])

    @pl.when(i == n_ctx)
    def _decode_arm_and_flush():
        kd = kd_ref[0]                # (ld, hd) bf16
        vd = vd_ref[0]
        sd = jax.lax.dot_general(
            q, kd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (rows, ld)
        sd = sd + bias_ref[...]
        row_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 0) // pn
        col_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 1) // c_d
        sd = jnp.where(row_s == col_s, sd, NEG_INF)

        acc, l_new = _online_update(sd, vd, acc_scr, m_scr, l_scr)
        out_ref[0] = (acc / jnp.maximum(l_new, 1e-30)).astype(out_ref.dtype)


def paged_fused_bifurcated_decode_q8(
    q: jnp.ndarray,          # (g, rows, hd)  rows = b * p * n
    k_pages_q: jnp.ndarray,  # (P, g, pm, hd) int8 — quantized page pool
    v_pages_q: jnp.ndarray,  # (P, g, pm, hd) int8
    k_scale_pages: jnp.ndarray,  # (P, g, pm) f32 — logit scale pre-folded
    v_scale_pages: jnp.ndarray,  # (P, g, pm) f32
    page_ids: jnp.ndarray,   # (max_pages,) i32
    page_segs: jnp.ndarray,  # (max_pages,) i32
    n_live: jnp.ndarray,     # (1,) i32
    path_rows: jnp.ndarray,  # (depth, rows, 128) i32
    page_bias: jnp.ndarray,  # (max_pages, pm) f32
    k_dec: jnp.ndarray,      # (g, b * c_d, hd) bf16
    v_dec: jnp.ndarray,      # (g, b * c_d, hd)
    dec_bias: jnp.ndarray,   # (1, b * c_d) f32
    *,
    scale: float,
    c_d: int,
    pn: int,
    interpret: bool = True,
) -> jnp.ndarray:
    """Single-pallas_call quantized PAGED decode: the live pool pages
    stream as int8 + f32 scale pages (half the dominant HBM term) walked
    by the same prefetched page list, no dequantized KV tensor or fp32
    partial ever in HBM. Bit-identical to
    ``tree_fused_bifurcated_decode_q8`` on the same logical contents when
    ``pm`` equals its ``block_m``."""
    depth = path_rows.shape[0]
    g, rows, hd = q.shape
    pm = k_pages_q.shape[2]
    max_pages = page_ids.shape[0]
    # unit second-minor axes: Mosaic's block rule (module notes)
    page_bias = page_bias[:, None, :]
    k_scale_pages = k_scale_pages[:, :, None, :]
    v_scale_pages = v_scale_pages[:, :, None, :]

    ld = k_dec.shape[1]
    ld_pad = (-ld) % 128   # lane-align the decode tile
    if ld_pad:
        k_dec = jnp.pad(k_dec, ((0, 0), (0, ld_pad), (0, 0)))
        v_dec = jnp.pad(v_dec, ((0, 0), (0, ld_pad), (0, 0)))
        dec_bias = jnp.pad(dec_bias, ((0, 0), (0, ld_pad)),
                           constant_values=NEG_INF)
    ld_full = ld + ld_pad

    kernel = functools.partial(
        _paged_fused_q8_kernel, scale=scale, c_d=c_d, pn=pn, depth=depth
    )
    last = max_pages - 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(g, max_pages + 1),
        in_specs=[
            pl.BlockSpec((1, rows, hd),
                         lambda gk, i, pid, seg, nl: (gk, 0, 0)),
            pl.BlockSpec((1, 1, pm, hd),
                         lambda gk, i, pid, seg, nl:
                         (pid[jnp.minimum(i, last)], gk, 0, 0)),
            pl.BlockSpec((1, 1, pm, hd),
                         lambda gk, i, pid, seg, nl:
                         (pid[jnp.minimum(i, last)], gk, 0, 0)),
            pl.BlockSpec((1, 1, 1, pm),
                         lambda gk, i, pid, seg, nl:
                         (pid[jnp.minimum(i, last)], gk, 0, 0)),
            pl.BlockSpec((1, 1, 1, pm),
                         lambda gk, i, pid, seg, nl:
                         (pid[jnp.minimum(i, last)], gk, 0, 0)),
            pl.BlockSpec((depth, rows, 128),
                         lambda gk, i, pid, seg, nl: (0, 0, 0)),
            pl.BlockSpec((1, 1, pm),
                         lambda gk, i, pid, seg, nl:
                         (jnp.minimum(i, last), 0, 0)),
            pl.BlockSpec((1, ld_full, hd),
                         lambda gk, i, pid, seg, nl: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full, hd),
                         lambda gk, i, pid, seg, nl: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full),
                         lambda gk, i, pid, seg, nl: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, hd),
                               lambda gk, i, pid, seg, nl: (gk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, rows, hd), q.dtype),
        interpret=interpret,
    )(page_ids, page_segs, n_live,
      q, k_pages_q, v_pages_q, k_scale_pages, v_scale_pages,
      path_rows, page_bias, k_dec, v_dec, dec_bias)
    return out


# ---------------------------------------------------------------------------
# Packed work-queue kernels: decode page-reads + piggybacked prefill tiles
# in one launch
# ---------------------------------------------------------------------------
#
# Descriptor format (all scalar-prefetched i32, built by ops.packed_work_queue
# from runtime data — admissions/retirements/chunk progress never recompile):
#
#   kind[i]  0 = pool page (decode context read), 1 = fresh prefill tile
#   seg[i]   segment id the entry belongs to; prefill tiles use a PSEUDO
#            segment id carried only by the chunk rows' extra path level
#   pdma[i]  pool-page DMA index; fresh entries PIN to the previous page
#            (revisiting rule -> no DMA)
#   fdma[i]  fresh-tile DMA index; page entries pin symmetrically
#   pos[i]   absolute token position of the entry's column 0 (pages: 0 —
#            their masking is wholly via ent_bias + path membership)
#   n_ent    live entry count (structural early exit past it)
#
# Per-row operands: path_rows carries one EXTRA level holding the pseudo
# segment for chunk rows (-1 for decode rows), row_pos the per-row absolute
# position for the causal mask over fresh tiles (decode rows: don't-care),
# row_slot the decode-arm slot id (chunk rows: -1, so they take nothing
# from the decode arm — their columns go NEG_INF and contribute
# exp(NEG_INF - m) == 0 to the shared running state).


def _packed_fused_kernel(
    *refs,
    scale: float,
    c_d: int,
    depth: int,
    has_carry: bool,
    emit_partials: bool,
):
    """Work-queue generalization of ``_paged_fused_kernel``: grid step i
    processes queue entry i — a pool page or a fresh prefill tile, selected
    in-register by ``kind`` while BOTH DMA streams pin their unused side to
    the previous block (revisiting rule ⇒ one real copy per step). The
    per-entry op sequence (scale, entry bias, path membership, online
    update) is the paged kernel's exactly, plus one causal term that is
    vacuously true for pages — which is what makes a decode-only queue
    bit-identical.

    ``has_carry`` seeds the fp32 scratch from a previous launch's raw
    (acc, m, l) instead of the identity; ``emit_partials`` flushes raw
    state instead of running the decode arm. Both are static, so the
    default single-launch kernel keeps the no-spill structure untouched."""
    (kind_ref, seg_ref, pdma_ref, fdma_ref, pos_ref, nent_ref) = refs[:6]
    idx = 6
    (q_ref, k_ref, v_ref, kf_ref, vf_ref,
     path_ref, eb_ref, rpos_ref, rslot_ref) = refs[idx:idx + 9]
    idx += 9
    if has_carry:
        acc0_ref, m0_ref, l0_ref = refs[idx:idx + 3]
        idx += 3
    if emit_partials:
        accout_ref, mout_ref, lout_ref = refs[idx:idx + 3]
        idx += 3
    else:
        kd_ref, vd_ref, bias_ref = refs[idx:idx + 3]
        out_ref = refs[idx + 3]
        idx += 4
    acc_scr, m_scr, l_scr = refs[idx:idx + 3]

    i = pl.program_id(1)
    n_ctx = pl.num_programs(1) - 1   # queue steps; last = decode arm/flush

    @pl.when(i == 0)
    def _init():
        if has_carry:
            acc_scr[...] = acc0_ref[0]
            m_scr[...] = m0_ref[0]
            l_scr[...] = l0_ref[0]
        else:
            acc_scr[...] = jnp.zeros_like(acc_scr)
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                      # (rows, hd)

    @pl.when((i < n_ctx) & (i < nent_ref[0]))
    def _queue_entry():
        is_page = kind_ref[i] == 0
        # in-register select between the two pinned DMA streams — the
        # unused one holds the PREVIOUS block (no copy moved for it).
        k = jnp.where(is_page, k_ref[0, 0], kf_ref[0, 0])   # (pm, hd)
        v = jnp.where(is_page, v_ref[0, 0], vf_ref[0, 0])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                      # (rows, pm)
        s = s + eb_ref[0]              # ragged tail / chunk-length bias
        seg = seg_ref[i]
        assigned = path_ref[0][:, :1] == seg   # (rows, 1)
        for lvl in range(1, depth):
            assigned |= path_ref[lvl][:, :1] == seg
        # causal mask for fresh tiles: entry columns live at absolute
        # positions pos[i]..pos[i]+pm-1 and a row may only attend columns
        # at-or-before its own position. Pages: vacuously true.
        cols = pos_ref[i] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = assigned & (is_page | (cols <= rpos_ref[:, :1]))
        s = jnp.where(ok, s, NEG_INF)
        _online_update(s, v, acc_scr, m_scr, l_scr)

    @pl.when(i == n_ctx)
    def _final_step():
        if emit_partials:
            accout_ref[0] = acc_scr[...]
            mout_ref[0] = m_scr[...]
            lout_ref[0] = l_scr[...]
        else:
            kd = kd_ref[0]                # (ld, hd)
            vd = vd_ref[0]
            sd = jax.lax.dot_general(
                q, kd, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                      # (rows, ld)
            sd = sd + bias_ref[...]        # slot validity + ld padding
            col_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 1) // c_d
            # row_slot replaces the paged kernel's iota//pn: decode rows
            # carry their slot id (identical values), chunk rows carry -1
            # (never a valid column slot -> zero contribution).
            sd = jnp.where(rslot_ref[:, :1] == col_s, sd, NEG_INF)

            acc, l_new = _online_update(sd, vd, acc_scr, m_scr, l_scr)
            out_ref[0] = (
                acc / jnp.maximum(l_new, 1e-30)
            ).astype(out_ref.dtype)


def _packed_specs(
    rows, hd, pm, depth, max_q, ld_full, g,
    *, has_carry, emit_partials, q8,
):
    """Shared BlockSpec scaffolding for the packed kernels. Index-map args
    after the grid indices are the six prefetch refs (kind, seg, pdma,
    fdma, pos, n_ent)."""
    last = max_q - 1
    in_specs = [
        pl.BlockSpec((1, rows, hd),
                     lambda gk, i, kn, sg, pd, fd, ps, ne: (gk, 0, 0)),
        # pool-page walk: block index = prefetched pdma entry. Fresh-tile
        # steps (and the final step) pin to the previous page — no DMA.
        pl.BlockSpec((1, 1, pm, hd),
                     lambda gk, i, kn, sg, pd, fd, ps, ne:
                     (pd[jnp.minimum(i, last)], gk, 0, 0)),
        pl.BlockSpec((1, 1, pm, hd),
                     lambda gk, i, kn, sg, pd, fd, ps, ne:
                     (pd[jnp.minimum(i, last)], gk, 0, 0)),
    ]
    if q8:
        in_specs += [
            pl.BlockSpec((1, 1, 1, pm),
                         lambda gk, i, kn, sg, pd, fd, ps, ne:
                         (pd[jnp.minimum(i, last)], gk, 0, 0)),
            pl.BlockSpec((1, 1, 1, pm),
                         lambda gk, i, kn, sg, pd, fd, ps, ne:
                         (pd[jnp.minimum(i, last)], gk, 0, 0)),
        ]
    in_specs += [
        # fresh-tile walk: the symmetric pinned stream (bf16 either way).
        pl.BlockSpec((1, 1, pm, hd),
                     lambda gk, i, kn, sg, pd, fd, ps, ne:
                     (fd[jnp.minimum(i, last)], gk, 0, 0)),
        pl.BlockSpec((1, 1, pm, hd),
                     lambda gk, i, kn, sg, pd, fd, ps, ne:
                     (fd[jnp.minimum(i, last)], gk, 0, 0)),
        pl.BlockSpec((depth, rows, 128),
                     lambda gk, i, kn, sg, pd, fd, ps, ne: (0, 0, 0)),
        pl.BlockSpec((1, 1, pm),
                     lambda gk, i, kn, sg, pd, fd, ps, ne:
                     (jnp.minimum(i, last), 0, 0)),
        pl.BlockSpec((rows, 128),
                     lambda gk, i, kn, sg, pd, fd, ps, ne: (0, 0)),
        pl.BlockSpec((rows, 128),
                     lambda gk, i, kn, sg, pd, fd, ps, ne: (0, 0)),
    ]
    if has_carry:
        in_specs += [
            pl.BlockSpec((1, rows, hd),
                         lambda gk, i, kn, sg, pd, fd, ps, ne: (gk, 0, 0)),
            pl.BlockSpec((1, rows, 128),
                         lambda gk, i, kn, sg, pd, fd, ps, ne: (gk, 0, 0)),
            pl.BlockSpec((1, rows, 128),
                         lambda gk, i, kn, sg, pd, fd, ps, ne: (gk, 0, 0)),
        ]
    if emit_partials:
        out_specs = [
            pl.BlockSpec((1, rows, hd),
                         lambda gk, i, kn, sg, pd, fd, ps, ne: (gk, 0, 0)),
            pl.BlockSpec((1, rows, 128),
                         lambda gk, i, kn, sg, pd, fd, ps, ne: (gk, 0, 0)),
            pl.BlockSpec((1, rows, 128),
                         lambda gk, i, kn, sg, pd, fd, ps, ne: (gk, 0, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((g, rows, hd), jnp.float32),
            jax.ShapeDtypeStruct((g, rows, 128), jnp.float32),
            jax.ShapeDtypeStruct((g, rows, 128), jnp.float32),
        ]
    else:
        in_specs += [
            pl.BlockSpec((1, ld_full, hd),
                         lambda gk, i, kn, sg, pd, fd, ps, ne: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full, hd),
                         lambda gk, i, kn, sg, pd, fd, ps, ne: (gk, 0, 0)),
            pl.BlockSpec((1, ld_full),
                         lambda gk, i, kn, sg, pd, fd, ps, ne: (0, 0)),
        ]
        out_specs = pl.BlockSpec(
            (1, rows, hd),
            lambda gk, i, kn, sg, pd, fd, ps, ne: (gk, 0, 0))
        out_shape = None   # caller supplies (needs q.dtype)
    scratch_shapes = [
        pltpu.VMEM((rows, hd), jnp.float32),
        pltpu.VMEM((rows, 128), jnp.float32),
        pltpu.VMEM((rows, 128), jnp.float32),
    ]
    return in_specs, out_specs, out_shape, scratch_shapes


def _pad_decode_tile(k_dec, v_dec, dec_bias):
    ld = k_dec.shape[1]
    ld_pad = (-ld) % 128   # lane-align the decode tile
    if ld_pad:
        k_dec = jnp.pad(k_dec, ((0, 0), (0, ld_pad), (0, 0)))
        v_dec = jnp.pad(v_dec, ((0, 0), (0, ld_pad), (0, 0)))
        dec_bias = jnp.pad(dec_bias, ((0, 0), (0, ld_pad)),
                           constant_values=NEG_INF)
    return k_dec, v_dec, dec_bias, ld + ld_pad


def packed_fused_bifurcated_decode(
    q: jnp.ndarray,          # (g, rows, hd)  decode rows ++ chunk rows
    k_pages: jnp.ndarray,    # (P, g, pm, hd) — head-major page pool
    v_pages: jnp.ndarray,    # (P, g, pm, hd)
    k_fresh: jnp.ndarray,    # (F, g, pm, hd) — prefill-chunk KV tiles
    v_fresh: jnp.ndarray,    # (F, g, pm, hd)
    ent_kind: jnp.ndarray,   # (max_q,) i32 — 0 page / 1 fresh tile
    ent_seg: jnp.ndarray,    # (max_q,) i32 — owning (pseudo-)segment
    ent_pdma: jnp.ndarray,   # (max_q,) i32 — pool DMA stream (pinned)
    ent_fdma: jnp.ndarray,   # (max_q,) i32 — fresh DMA stream (pinned)
    ent_pos: jnp.ndarray,    # (max_q,) i32 — absolute position of col 0
    n_ent: jnp.ndarray,      # (1,) i32 — live entry count
    path_rows: jnp.ndarray,  # (depth, rows, 128) i32 — incl. pseudo level
    ent_bias: jnp.ndarray,   # (max_q, pm) f32 — per-entry ragged bias
    row_pos: jnp.ndarray,    # (rows, 128) i32 — per-row absolute position
    row_slot: jnp.ndarray,   # (rows, 128) i32 — decode slot id / -1
    k_dec: jnp.ndarray = None,   # (g, b * c_d, hd); None iff emit_partials
    v_dec: jnp.ndarray = None,
    dec_bias: jnp.ndarray = None,  # (1, b * c_d) f32
    *,
    scale: float,
    c_d: int,
    interpret: bool = True,
    carry: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray] = None,
    emit_partials: bool = False,
):
    """Single-pallas_call PACKED heterogeneous step: one work-queue grid
    streams decode page-reads AND chunked suffix-prefill tiles, all rows
    sharing the fp32 VMEM running state; the decode arm + normalize fold
    into the final step. On a decode-only queue (all kind == 0) this is
    bit-identical to ``paged_fused_bifurcated_decode``: the where-selects
    resolve to the page stream, the causal term is vacuously true, the
    extra path level is -1 for every row, and ``row_slot`` carries exactly
    ``iota // pn``.

    ``carry=(acc, m, l)`` / ``emit_partials=True`` chain launches exactly
    for queues longer than one grid envelope; the chained result is
    bit-identical to a single launch because the raw fp32 state round-trips
    losslessly and the per-entry op sequence is unchanged."""
    depth = path_rows.shape[0]
    g, rows, hd = q.shape
    pm = k_pages.shape[2]
    max_q = ent_kind.shape[0]
    # unit second-minor axes: Mosaic's block rule (module notes)
    ent_bias = ent_bias[:, None, :]

    ld_full = 0
    if not emit_partials:
        k_dec, v_dec, dec_bias, ld_full = _pad_decode_tile(
            k_dec, v_dec, dec_bias)

    kernel = functools.partial(
        _packed_fused_kernel, scale=scale, c_d=c_d, depth=depth,
        has_carry=carry is not None, emit_partials=emit_partials,
    )
    in_specs, out_specs, out_shape, scratch = _packed_specs(
        rows, hd, pm, depth, max_q, ld_full, g,
        has_carry=carry is not None, emit_partials=emit_partials, q8=False,
    )
    if out_shape is None:
        out_shape = jax.ShapeDtypeStruct((g, rows, hd), q.dtype)

    operands = [q, k_pages, v_pages, k_fresh, v_fresh,
                path_rows, ent_bias, row_pos, row_slot]
    if carry is not None:
        operands += list(carry)
    if not emit_partials:
        operands += [k_dec, v_dec, dec_bias]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(g, max_q + 1),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(ent_kind, ent_seg, ent_pdma, ent_fdma, ent_pos, n_ent, *operands)


def _packed_fused_q8_kernel(
    *refs,
    scale: float,
    c_d: int,
    depth: int,
    has_carry: bool,
    emit_partials: bool,
):
    """Quantized twin of ``_packed_fused_kernel``: pool pages stream int8 +
    f32 scales (logit scale pre-folded into k scales) while fresh prefill
    tiles stay bf16 — the per-entry scale/p_scale select keeps the pool
    side bit-identical to ``_paged_fused_q8_kernel`` on decode-only
    queues."""
    (kind_ref, seg_ref, pdma_ref, fdma_ref, pos_ref, nent_ref) = refs[:6]
    idx = 6
    (q_ref, k_ref, v_ref, ks_ref, vs_ref, kf_ref, vf_ref,
     path_ref, eb_ref, rpos_ref, rslot_ref) = refs[idx:idx + 11]
    idx += 11
    if has_carry:
        acc0_ref, m0_ref, l0_ref = refs[idx:idx + 3]
        idx += 3
    if emit_partials:
        accout_ref, mout_ref, lout_ref = refs[idx:idx + 3]
        idx += 3
    else:
        kd_ref, vd_ref, bias_ref = refs[idx:idx + 3]
        out_ref = refs[idx + 3]
        idx += 4
    acc_scr, m_scr, l_scr = refs[idx:idx + 3]

    i = pl.program_id(1)
    n_ctx = pl.num_programs(1) - 1

    @pl.when(i == 0)
    def _init():
        if has_carry:
            acc_scr[...] = acc0_ref[0]
            m_scr[...] = m0_ref[0]
            l_scr[...] = l0_ref[0]
        else:
            acc_scr[...] = jnp.zeros_like(acc_scr)
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                      # (rows, hd)

    @pl.when((i < n_ctx) & (i < nent_ref[0]))
    def _queue_entry():
        is_page = kind_ref[i] == 0
        k = jnp.where(is_page,
                      k_ref[0, 0].astype(jnp.float32),
                      kf_ref[0, 0].astype(jnp.float32))
        v = jnp.where(is_page,
                      v_ref[0, 0].astype(jnp.float32),
                      vf_ref[0, 0].astype(jnp.float32))
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                              # raw q·K — scale folded below
        # pages: per-token k scales with the logit scale pre-folded;
        # fresh bf16 tiles: the plain logit scale.
        s = s * jnp.where(is_page, ks_ref[0, 0], jnp.float32(scale))
        s = s + eb_ref[0]
        seg = seg_ref[i]
        assigned = path_ref[0][:, :1] == seg
        for lvl in range(1, depth):
            assigned |= path_ref[lvl][:, :1] == seg
        cols = pos_ref[i] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = assigned & (is_page | (cols <= rpos_ref[:, :1]))
        s = jnp.where(ok, s, NEG_INF)
        p_scale = jnp.where(is_page, vs_ref[0, 0],
                            jnp.ones_like(vs_ref[0, 0]))
        _online_update(s, v, acc_scr, m_scr, l_scr, p_scale=p_scale)

    @pl.when(i == n_ctx)
    def _final_step():
        if emit_partials:
            accout_ref[0] = acc_scr[...]
            mout_ref[0] = m_scr[...]
            lout_ref[0] = l_scr[...]
        else:
            kd = kd_ref[0]                # (ld, hd) bf16
            vd = vd_ref[0]
            sd = jax.lax.dot_general(
                q, kd, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            sd = sd + bias_ref[...]
            col_s = jax.lax.broadcasted_iota(jnp.int32, sd.shape, 1) // c_d
            sd = jnp.where(rslot_ref[:, :1] == col_s, sd, NEG_INF)

            acc, l_new = _online_update(sd, vd, acc_scr, m_scr, l_scr)
            out_ref[0] = (
                acc / jnp.maximum(l_new, 1e-30)
            ).astype(out_ref.dtype)


def packed_fused_bifurcated_decode_q8(
    q: jnp.ndarray,          # (g, rows, hd)
    k_pages_q: jnp.ndarray,  # (P, g, pm, hd) int8
    v_pages_q: jnp.ndarray,  # (P, g, pm, hd) int8
    k_scale_pages: jnp.ndarray,  # (P, g, pm) f32 — logit scale pre-folded
    v_scale_pages: jnp.ndarray,  # (P, g, pm) f32
    k_fresh: jnp.ndarray,    # (F, g, pm, hd) bf16 — chunk KV stays full
    v_fresh: jnp.ndarray,    # (F, g, pm, hd) bf16
    ent_kind: jnp.ndarray,   # (max_q,) i32
    ent_seg: jnp.ndarray,    # (max_q,) i32
    ent_pdma: jnp.ndarray,   # (max_q,) i32
    ent_fdma: jnp.ndarray,   # (max_q,) i32
    ent_pos: jnp.ndarray,    # (max_q,) i32
    n_ent: jnp.ndarray,      # (1,) i32
    path_rows: jnp.ndarray,  # (depth, rows, 128) i32
    ent_bias: jnp.ndarray,   # (max_q, pm) f32
    row_pos: jnp.ndarray,    # (rows, 128) i32
    row_slot: jnp.ndarray,   # (rows, 128) i32
    k_dec: jnp.ndarray = None,   # (g, b * c_d, hd) bf16
    v_dec: jnp.ndarray = None,
    dec_bias: jnp.ndarray = None,
    *,
    scale: float,
    c_d: int,
    interpret: bool = True,
    carry: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray] = None,
    emit_partials: bool = False,
):
    """Quantized packed heterogeneous step: int8 pool pages + bf16 fresh
    prefill tiles on one work-queue grid. Bit-identical to
    ``paged_fused_bifurcated_decode_q8`` on decode-only queues (the scale
    and p_scale selects resolve to the pool-page values)."""
    depth = path_rows.shape[0]
    g, rows, hd = q.shape
    pm = k_pages_q.shape[2]
    max_q = ent_kind.shape[0]
    # unit second-minor axes: Mosaic's block rule (module notes)
    ent_bias = ent_bias[:, None, :]
    k_scale_pages = k_scale_pages[:, :, None, :]
    v_scale_pages = v_scale_pages[:, :, None, :]

    ld_full = 0
    if not emit_partials:
        k_dec, v_dec, dec_bias, ld_full = _pad_decode_tile(
            k_dec, v_dec, dec_bias)

    kernel = functools.partial(
        _packed_fused_q8_kernel, scale=scale, c_d=c_d, depth=depth,
        has_carry=carry is not None, emit_partials=emit_partials,
    )
    in_specs, out_specs, out_shape, scratch = _packed_specs(
        rows, hd, pm, depth, max_q, ld_full, g,
        has_carry=carry is not None, emit_partials=emit_partials, q8=True,
    )
    if out_shape is None:
        out_shape = jax.ShapeDtypeStruct((g, rows, hd), q.dtype)

    operands = [q, k_pages_q, v_pages_q, k_scale_pages, v_scale_pages,
                k_fresh, v_fresh, path_rows, ent_bias, row_pos, row_slot]
    if carry is not None:
        operands += list(carry)
    if not emit_partials:
        operands += [k_dec, v_dec, dec_bias]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(g, max_q + 1),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(ent_kind, ent_seg, ent_pdma, ent_fdma, ent_pos, n_ent, *operands)


# ---------------------------------------------------------------------------
# Two-pass building block (context arm only; legacy / oracle path)
# ---------------------------------------------------------------------------

def _ctx_flash_kernel(
    q_ref,      # (1, rows, hd)
    k_ref,      # (1, block_m, hd)
    v_ref,      # (1, block_m, hd)
    acc_ref,    # out: (1, rows, hd) f32 — unnormalized value accumulator
    m_ref,      # out: (1, rows, 128) f32 — running max (lane-replicated)
    l_ref,      # out: (1, rows, 128) f32 — running sumexp
    acc_scr,    # scratch (rows, hd) f32
    m_scr,      # scratch (rows, 128) f32
    l_scr,      # scratch (rows, 128) f32
    *,
    scale: float,
    m_c: int,
    block_m: int,
):
    i = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                      # (rows, hd)
    k = k_ref[0]                      # (block_m, hd)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                          # (rows, block_m)

    # mask the zero-padded K tail of the last block
    pos = i * block_m + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < m_c, s, NEG_INF)
    _online_update(s, v, acc_scr, m_scr, l_scr)

    @pl.when(i == nb - 1)
    def _flush():
        acc_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


def context_flash_partials(
    q: jnp.ndarray,        # (g, rows, hd)  rows = b * p * n
    k_ctx: jnp.ndarray,    # (g, m_c, hd)
    v_ctx: jnp.ndarray,    # (g, m_c, hd)
    *,
    scale: float,
    block_m: int = 512,
    interpret: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns flash partials (acc (g,rows,hd) f32, m (g,rows), l (g,rows)).

    Two-pass path: the partials are spilled to HBM and merged with the
    einsum decode arm on the host side (ops.py, ``two_pass=True``). The
    fused kernel above makes this spill unnecessary.
    """
    g, rows, hd = q.shape
    m_c = k_ctx.shape[1]
    block_m = min(block_m, max(128, m_c))
    pad = (-m_c) % block_m
    if pad:
        k_ctx = jnp.pad(k_ctx, ((0, 0), (0, pad), (0, 0)))
        v_ctx = jnp.pad(v_ctx, ((0, 0), (0, pad), (0, 0)))
    nb = k_ctx.shape[1] // block_m

    kernel = functools.partial(
        _ctx_flash_kernel, scale=scale, m_c=m_c, block_m=block_m
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid=(g, nb),
        in_specs=[
            pl.BlockSpec((1, rows, hd), lambda gi, i: (gi, 0, 0)),
            pl.BlockSpec((1, block_m, hd), lambda gi, i: (gi, i, 0)),
            pl.BlockSpec((1, block_m, hd), lambda gi, i: (gi, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, hd), lambda gi, i: (gi, 0, 0)),
            pl.BlockSpec((1, rows, 128), lambda gi, i: (gi, 0, 0)),
            pl.BlockSpec((1, rows, 128), lambda gi, i: (gi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, rows, hd), jnp.float32),
            jax.ShapeDtypeStruct((g, rows, 128), jnp.float32),
            jax.ShapeDtypeStruct((g, rows, 128), jnp.float32),
        ],
        scratch_shapes=[
            # fp32 VMEM accumulators — the whole working set per grid step is
            # rows*hd (q) + 2*block_m*hd (kv) + rows*(hd+256) (scratch) floats;
            # with rows=256, hd=128, block_m=512 that is ~0.9 MB << 16 MB VMEM.
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_ctx, v_ctx)
    return acc, m[..., 0], l[..., 0]
