"""Jitted wrappers + dispatch for the Pallas kernels.

``bifurcated_decode_attention`` is the deployable path. By default it lowers
to the SINGLE-pass fused kernel (``kernels.bifurcated_decode.
fused_bifurcated_decode``): one ``pallas_call`` streams the K_c/V_c blocks,
folds the per-sample decode arm into the same fp32 VMEM running
``(max, sumexp, acc)`` state with the slot mask applied in-kernel, and
writes the normalized output — no fp32 partials and no logits ever touch
HBM, and no host-side merge or transposes remain on the hot path.

``two_pass=True`` is the escape hatch to the historical pipeline: the
context arm runs the partials kernel (spilling fp32 ``acc/m/l`` to HBM),
the small decode arm stays on XLA einsums, and the two halves merge with
the exact two-way online-softmax combine on the host.

Both paths accept the framework's cache layouts ("mgk" ``(m_c, g, hd)`` or
head-major "gmk" ``(g, m_c, hd)`` — zero-copy for the kernel) and any
number ``n >= 1`` of fresh query positions per sample (speculative /
draft-token decoding): ``n`` is folded into the kernel's row dimension
(``rows = b*p*n``), matching ``core.bifurcated_attention`` semantics under
a shared ``(b, C_d)`` decode mask. NOTE that a shared mask means attention
WITHIN the fresh draft block is bidirectional — the framework's existing
n>1 semantics (models/blocks.py builds exactly this mask); per-draft-token
causal masks ((b, n, C_d) form) are not expressible in the fused kernel yet.

``interpret=None`` (the default) resolves by backend: compiled Mosaic on
TPU, interpret mode elsewhere — so the model/serve stack gets the real
kernel on hardware without threading a flag through every layer.

``bifurcated_decode_attention_q8`` is the quantized-context twin: the same
single-pass fused structure, but the context arm streams int8 K_c/V_c plus
per-(token, head) scales (k_scale pre-folded with the logit scale) and
dequantizes in-register — the context read costs half the bytes.

``grouped_bifurcated_decode_attention`` / ``..._q8`` are the multi-prefix
FOREST dispatchers: G shared-context segments in one batch with a
``(b,) -> group`` slot assignment and ragged per-group lengths — all
runtime data, so one compile serves any admit/retire sequence of the
continuous-batching engine (runtime/serve.ForestServeEngine). At G == 1
they are token-identical to the single-prefix dispatchers.

``tree_bifurcated_decode_attention`` / ``..._q8`` are the hierarchical
CASCADE dispatchers: N trie-node segments and a static-depth ``(depth, b)``
slot -> node path table (-1 = unused level), so a slot attends over the
concatenation of every node on its path. The path table, node lengths and
node contents are all runtime data; ``depth`` is the only new static —
one compile per trie depth. At depth == 1 they are token-identical to the
grouped dispatchers (and hence, with one node, to the single-prefix ones).

``paged_bifurcated_decode_attention`` / ``..._q8`` are the PAGED-substrate
dispatchers (core/paged.py): context KV in a head-major page pool +
per-segment block tables, the kernel walking a prefix-counted live-page
list (``live_page_list``) so free segments and dead capacity are never
DMA'd. The dense dispatchers above remain the escape hatch and the
differential oracles for them.

``packed_bifurcated_decode_attention`` / ``..._q8`` are the PACKED
heterogeneous-step dispatchers: ``packed_work_queue`` generalizes the
live-page list into a work-queue of (kind, seg, page/offset) descriptors
— decode page-reads AND chunked suffix-prefill tiles — and one kernel
launch walks it, the prefill-chunk query rows joining the decode rows in
the same fp32 running state (a separate prefill dispatch disappears from
the step). Everything in the queue is traced runtime data: chunk sizes,
admissions mid-stream, and retirements never recompile. On a decode-only
queue the result is bit-identical to the paged dispatchers; with a chunk
attached the chunk half equals a causal suffix prefill over
[matched ancestors ⊕ chunk]. ``entries_per_launch`` statically splits
queues longer than one grid envelope into chained launches (raw fp32
carry in HBM between launches — the one deliberate no-spill exception).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.bifurcated_decode import (
    context_flash_partials,
    fused_bifurcated_decode,
    fused_bifurcated_decode_q8,
    grouped_fused_bifurcated_decode,
    grouped_fused_bifurcated_decode_q8,
    packed_fused_bifurcated_decode,
    packed_fused_bifurcated_decode_q8,
    paged_fused_bifurcated_decode,
    paged_fused_bifurcated_decode_q8,
    tree_fused_bifurcated_decode,
    tree_fused_bifurcated_decode_q8,
    walk_head_block,
)

NEG_INF = -1e30


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_m", "interpret", "ctx_layout", "two_pass"),
)
def bifurcated_decode_attention(
    q: jnp.ndarray,         # (b, g, p, n, hd) — framework decode layout
    k_ctx: jnp.ndarray,     # (m_c, g, hd) "mgk" or (g, m_c, hd) "gmk"
    v_ctx: jnp.ndarray,
    k_dec: jnp.ndarray,     # (b, c_d, g, hd)
    v_dec: jnp.ndarray,
    dec_mask: jnp.ndarray,  # (b, c_d) bool
    *,
    scale: Optional[float] = None,
    block_m: int = 512,
    interpret: Optional[bool] = None,
    ctx_layout: str = "mgk",
    two_pass: bool = False,
) -> jnp.ndarray:
    """Single-prefix bifurcated decode dispatcher (the deployable path).

    Shapes/dtypes (framework layouts; any float dtype, bf16 in serving):
      q:        (b, g, p, n, hd) — b samples, g kv heads, p query heads
                per kv head, n fresh positions (speculative drafts).
      k_ctx/v_ctx: shared context, NO batch axis — (m_c, g, hd) under
                ``ctx_layout="mgk"`` (sequence-major) or (g, m_c, hd)
                under "gmk" (head-major; zero-copy for the kernel).
      k_dec/v_dec: (b, c_d, g, hd) per-sample decode continuation.
      dec_mask: (b, c_d) bool — live decode slots.
    Returns (b, g, p, n, hd) in q's dtype, softmax-normalized over
    [context ⊕ live decode slots].

    Default lowers to the single-pass fused Pallas kernel (no fp32
    partials or logits in HBM); ``two_pass=True`` is the historical
    partials-spill + host-merge escape hatch. ``interpret=None`` resolves
    by backend (compiled Mosaic on TPU, interpret elsewhere)."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    scale = hd**-0.5 if scale is None else scale
    if interpret is None:  # static arg: resolved once at trace time
        interpret = jax.default_backend() != "tpu"

    # kernel-major query rows: r = (b_idx*p + p_idx)*n + n_idx
    qk = q.transpose(1, 0, 2, 3, 4).reshape(g, b * p * n, hd)
    if ctx_layout == "gmk":  # already kernel-major: zero-copy
        kc, vc = k_ctx, v_ctx
    else:
        kc = k_ctx.transpose(1, 0, 2)  # (g, m_c, hd)
        vc = v_ctx.transpose(1, 0, 2)

    if not two_pass:
        # ---- single-pass fused kernel: decode arm + merge in-kernel ----
        kd = k_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
        vd = v_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
        bias = jnp.where(dec_mask.reshape(1, b * c_d), 0.0, NEG_INF
                         ).astype(jnp.float32)
        out = fused_bifurcated_decode(
            qk, kc, vc, kd, vd, bias,
            scale=scale, c_d=c_d, pn=p * n,
            block_m=block_m, interpret=interpret,
        )  # (g, b*p*n, hd), normalized
        out = out.reshape(g, b, p, n, hd).transpose(1, 0, 2, 3, 4)
        return out.astype(q.dtype)

    # ---- two-pass escape hatch: partials kernel + einsum arm + merge ----
    acc_c, m_cx, l_c = context_flash_partials(
        qk, kc, vc, scale=scale, block_m=block_m, interpret=interpret
    )  # (g, b*p*n, hd), (g, b*p*n), (g, b*p*n)

    # decode arm: einsum partials (c_d is small)
    s_d = jnp.einsum("bgpnk,bmgk->bgpnm", q, k_dec).astype(jnp.float32)
    s_d = s_d * scale
    s_d = jnp.where(dec_mask[:, None, None, None, :], s_d, NEG_INF)
    m_d = jnp.max(s_d, axis=-1)
    m_d = jnp.maximum(m_d, NEG_INF / 2)
    e_d = jnp.exp(s_d - m_d[..., None])
    l_d = jnp.sum(e_d, axis=-1)
    acc_d = jnp.einsum(
        "bgpnm,bmgv->bgpnv", e_d.astype(v_dec.dtype), v_dec
    ).astype(jnp.float32)

    # exact two-way merge
    acc_cb = acc_c.reshape(g, b, p, n, hd).transpose(1, 0, 2, 3, 4)
    m_cb = m_cx.reshape(g, b, p, n).transpose(1, 0, 2, 3)
    l_cb = l_c.reshape(g, b, p, n).transpose(1, 0, 2, 3)
    m_star = jnp.maximum(m_cb, m_d)
    corr_c = jnp.exp(m_cb - m_star)
    corr_d = jnp.exp(m_d - m_star)
    l_tot = l_cb * corr_c + l_d * corr_d
    out = (acc_cb * corr_c[..., None] + acc_d * corr_d[..., None]) / l_tot[..., None]
    return out.astype(q.dtype)  # (b, g, p, n, hd)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_m", "interpret", "ctx_layout"),
)
def bifurcated_decode_attention_q8(
    q: jnp.ndarray,         # (b, g, p, n, hd) — framework decode layout
    k_ctx_q: jnp.ndarray,   # int8: (m_c, g, hd) "mgk" or (g, m_c, hd) "gmk"
    v_ctx_q: jnp.ndarray,
    k_scale_folded: jnp.ndarray,  # f32: (m_c, g) "mgk" or (g, m_c) "gmk";
    v_scale: jnp.ndarray,         #   MUST carry the logit scale pre-folded
    k_dec: jnp.ndarray,     # (b, c_d, g, hd) bf16
    v_dec: jnp.ndarray,
    dec_mask: jnp.ndarray,  # (b, c_d) bool
    *,
    scale: Optional[float] = None,
    block_m: int = 512,
    interpret: Optional[bool] = None,
    ctx_layout: str = "gmk",
) -> jnp.ndarray:
    """Quantized-context twin of ``bifurcated_decode_attention``: one
    pallas_call streams the int8 K_c/V_c blocks + per-(token, head) scales,
    dequantizes in-register, and merges the bf16 decode arm into the same
    fp32 VMEM running state. No dequantized KV tensor and no fp32 partials
    ever touch HBM. ``scale`` applies to the decode arm only — the context
    logit scale must arrive pre-folded in ``k_scale_folded`` (use
    ``quantize_ctx(k, fold_scale=hd**-0.5)`` / ``from_prefill``)."""
    k_scale = k_scale_folded
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    scale = hd**-0.5 if scale is None else scale
    if interpret is None:  # static arg: resolved once at trace time
        interpret = jax.default_backend() != "tpu"

    # kernel-major query rows: r = (b_idx*p + p_idx)*n + n_idx
    qk = q.transpose(1, 0, 2, 3, 4).reshape(g, b * p * n, hd)
    if ctx_layout == "gmk":  # already kernel-major: zero-copy
        kc, vc, ks, vs = k_ctx_q, v_ctx_q, k_scale, v_scale
    else:
        kc = k_ctx_q.transpose(1, 0, 2)  # (g, m_c, hd)
        vc = v_ctx_q.transpose(1, 0, 2)
        ks = k_scale.T                   # (g, m_c)
        vs = v_scale.T

    kd = k_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
    vd = v_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
    bias = jnp.where(dec_mask.reshape(1, b * c_d), 0.0, NEG_INF
                     ).astype(jnp.float32)
    out = fused_bifurcated_decode_q8(
        qk, kc, vc, ks, vs, kd, vd, bias,
        scale=scale, c_d=c_d, pn=p * n,
        block_m=block_m, interpret=interpret,
    )  # (g, b*p*n, hd), normalized
    out = out.reshape(g, b, p, n, hd).transpose(1, 0, 2, 3, 4)
    return out.astype(q.dtype)


def _forest_operands(q, group_ids, ctx_lens, k_dec, v_dec, dec_mask, m_c):
    """Shared grouped-dispatch plumbing: kernel-major q rows, lane-replicated
    row -> group assignment, per-group ragged context bias, group-major
    flattened decode arm + slot-validity bias."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    qk = q.transpose(1, 0, 2, 3, 4).reshape(g, b * p * n, hd)
    # row r = (b_idx*p + p_idx)*n + n_idx belongs to sample r // (p*n)
    row_group = jnp.broadcast_to(
        jnp.repeat(group_ids.astype(jnp.int32), p * n)[:, None],
        (b * p * n, 128))
    ctx_bias = jnp.where(
        jnp.arange(m_c)[None, :] < ctx_lens[:, None], 0.0, NEG_INF
    ).astype(jnp.float32)                        # (G, m_c)
    kd = k_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
    vd = v_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
    bias = jnp.where(dec_mask.reshape(1, b * c_d), 0.0, NEG_INF
                     ).astype(jnp.float32)
    return qk, row_group, ctx_bias, kd, vd, bias


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_m", "interpret", "ctx_layout"),
)
def grouped_bifurcated_decode_attention(
    q: jnp.ndarray,          # (b, g, p, n, hd) — framework decode layout
    k_ctx: jnp.ndarray,      # (G, m_c, g, hd) "mgk" or (G, g, m_c, hd) "gmk"
    v_ctx: jnp.ndarray,
    group_ids: jnp.ndarray,  # (b,) i32 — slot -> prefix-group assignment
    ctx_lens: jnp.ndarray,   # (G,) i32 — live (ragged) prefix lengths
    k_dec: jnp.ndarray,      # (b, c_d, g, hd)
    v_dec: jnp.ndarray,
    dec_mask: jnp.ndarray,   # (b, c_d) bool
    *,
    scale: Optional[float] = None,
    block_m: int = 512,
    interpret: Optional[bool] = None,
    ctx_layout: str = "gmk",
) -> jnp.ndarray:
    """Multi-prefix (forest) fused decode dispatcher: G shared-context
    segments in ONE batch, each decode slot assigned to one group via
    ``group_ids``. Lowers to the single-pallas_call grouped kernel — every
    group's K_c/V_c streams from HBM once per kv head, ragged tails and the
    row assignment are masked in-kernel, and at G == 1 the computation is
    token-identical to ``bifurcated_decode_attention`` (same block
    schedule, same online-softmax update order)."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    scale = hd**-0.5 if scale is None else scale
    if interpret is None:  # static arg: resolved once at trace time
        interpret = jax.default_backend() != "tpu"

    if ctx_layout == "gmk":  # already kernel-major: zero-copy
        kc, vc = k_ctx, v_ctx
    else:
        kc = k_ctx.transpose(0, 2, 1, 3)  # (G, g, m_c, hd)
        vc = v_ctx.transpose(0, 2, 1, 3)
    m_c = kc.shape[2]
    qk, row_group, ctx_bias, kd, vd, bias = _forest_operands(
        q, group_ids, ctx_lens, k_dec, v_dec, dec_mask, m_c)
    out = grouped_fused_bifurcated_decode(
        qk, kc, vc, row_group, ctx_bias, kd, vd, bias,
        scale=scale, c_d=c_d, pn=p * n,
        block_m=block_m, interpret=interpret,
    )  # (g, b*p*n, hd), normalized
    out = out.reshape(g, b, p, n, hd).transpose(1, 0, 2, 3, 4)
    return out.astype(q.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_m", "interpret", "ctx_layout"),
)
def grouped_bifurcated_decode_attention_q8(
    q: jnp.ndarray,          # (b, g, p, n, hd) — framework decode layout
    k_ctx_q: jnp.ndarray,    # int8: (G, m_c, g, hd) "mgk" | (G, g, m_c, hd)
    v_ctx_q: jnp.ndarray,
    k_scale_folded: jnp.ndarray,  # f32: (G, m_c, g) | (G, g, m_c); MUST
    v_scale: jnp.ndarray,         #   carry the logit scale pre-folded
    group_ids: jnp.ndarray,  # (b,) i32
    ctx_lens: jnp.ndarray,   # (G,) i32
    k_dec: jnp.ndarray,      # (b, c_d, g, hd) bf16
    v_dec: jnp.ndarray,
    dec_mask: jnp.ndarray,   # (b, c_d) bool
    *,
    scale: Optional[float] = None,
    block_m: int = 512,
    interpret: Optional[bool] = None,
    ctx_layout: str = "gmk",
) -> jnp.ndarray:
    """Quantized-context twin of ``grouped_bifurcated_decode_attention``:
    int8 context segments + per-(token, head) scales (k pre-folded with the
    logit scale), dequantized in-register inside the grouped kernel."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    scale = hd**-0.5 if scale is None else scale
    if interpret is None:  # static arg: resolved once at trace time
        interpret = jax.default_backend() != "tpu"

    if ctx_layout == "gmk":  # already kernel-major: zero-copy
        kc, vc = k_ctx_q, v_ctx_q
        ks, vs = k_scale_folded, v_scale
    else:
        kc = k_ctx_q.transpose(0, 2, 1, 3)   # (G, g, m_c, hd)
        vc = v_ctx_q.transpose(0, 2, 1, 3)
        ks = k_scale_folded.transpose(0, 2, 1)  # (G, g, m_c)
        vs = v_scale.transpose(0, 2, 1)
    m_c = kc.shape[2]
    qk, row_group, ctx_bias, kd, vd, bias = _forest_operands(
        q, group_ids, ctx_lens, k_dec, v_dec, dec_mask, m_c)
    out = grouped_fused_bifurcated_decode_q8(
        qk, kc, vc, ks, vs, row_group, ctx_bias, kd, vd, bias,
        scale=scale, c_d=c_d, pn=p * n,
        block_m=block_m, interpret=interpret,
    )  # (g, b*p*n, hd), normalized
    out = out.reshape(g, b, p, n, hd).transpose(1, 0, 2, 3, 4)
    return out.astype(q.dtype)


def _tree_operands(q, paths, node_lens, k_dec, v_dec, dec_mask, m_c):
    """Shared tree-dispatch plumbing: kernel-major q rows, lane-replicated
    per-level row -> node assignment, per-node ragged context bias,
    group-major flattened decode arm + slot-validity bias.

    ``paths`` is (depth, b) i32 (-1 = unused level); it expands to the
    kernel's (depth, rows, 128) lane-replicated table with row
    r = (b_idx*p + p_idx)*n + n_idx inheriting slot b_idx's path."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    depth = paths.shape[0]
    qk = q.transpose(1, 0, 2, 3, 4).reshape(g, b * p * n, hd)
    pr = jnp.repeat(paths.astype(jnp.int32), p * n, axis=1)  # (depth, rows)
    path_rows = jnp.broadcast_to(pr[:, :, None], (depth, b * p * n, 128))
    ctx_bias = jnp.where(
        jnp.arange(m_c)[None, :] < node_lens[:, None], 0.0, NEG_INF
    ).astype(jnp.float32)                        # (N, m_c)
    kd = k_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
    vd = v_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
    bias = jnp.where(dec_mask.reshape(1, b * c_d), 0.0, NEG_INF
                     ).astype(jnp.float32)
    return qk, path_rows, ctx_bias, kd, vd, bias


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_m", "interpret", "ctx_layout"),
)
def tree_bifurcated_decode_attention(
    q: jnp.ndarray,          # (b, g, p, n, hd) — framework decode layout
    k_ctx: jnp.ndarray,      # (N, m_c, g, hd) "mgk" or (N, g, m_c, hd) "gmk"
    v_ctx: jnp.ndarray,
    paths: jnp.ndarray,      # (depth, b) i32 — slot -> node id per trie
                             #   level, -1 = level unused by that slot
    node_lens: jnp.ndarray,  # (N,) i32 — live (ragged) node lengths
    k_dec: jnp.ndarray,      # (b, c_d, g, hd)
    v_dec: jnp.ndarray,
    dec_mask: jnp.ndarray,   # (b, c_d) bool
    *,
    scale: Optional[float] = None,
    block_m: int = 512,
    interpret: Optional[bool] = None,
    ctx_layout: str = "gmk",
) -> jnp.ndarray:
    """Hierarchical (prefix-trie / cascade) fused decode dispatcher: N
    trie-node segments in ONE batch, each decode slot attending over the
    CONCATENATION of the nodes on its ``paths`` column (system prompt ->
    few-shot template -> per-request prompt, etc.) plus its own decode arm.
    Lowers to the single-pallas_call tree kernel — every node's K/V streams
    from HBM once per kv head per step regardless of how many paths
    traverse it. All trie state (paths / node_lens / node contents) is
    runtime DATA; only ``depth`` (the path-table height) is static. At
    depth == 1 the computation is token-identical to
    ``grouped_bifurcated_decode_attention`` (same grid, same masking, same
    online-softmax update order)."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    scale = hd**-0.5 if scale is None else scale
    if interpret is None:  # static arg: resolved once at trace time
        interpret = jax.default_backend() != "tpu"

    if ctx_layout == "gmk":  # already kernel-major: zero-copy
        kc, vc = k_ctx, v_ctx
    else:
        kc = k_ctx.transpose(0, 2, 1, 3)  # (N, g, m_c, hd)
        vc = v_ctx.transpose(0, 2, 1, 3)
    m_c = kc.shape[2]
    qk, path_rows, ctx_bias, kd, vd, bias = _tree_operands(
        q, paths, node_lens, k_dec, v_dec, dec_mask, m_c)
    out = tree_fused_bifurcated_decode(
        qk, kc, vc, path_rows, ctx_bias, kd, vd, bias,
        scale=scale, c_d=c_d, pn=p * n,
        block_m=block_m, interpret=interpret,
    )  # (g, b*p*n, hd), normalized
    out = out.reshape(g, b, p, n, hd).transpose(1, 0, 2, 3, 4)
    return out.astype(q.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_m", "interpret", "ctx_layout"),
)
def tree_bifurcated_decode_attention_q8(
    q: jnp.ndarray,          # (b, g, p, n, hd) — framework decode layout
    k_ctx_q: jnp.ndarray,    # int8: (N, m_c, g, hd) "mgk" | (N, g, m_c, hd)
    v_ctx_q: jnp.ndarray,
    k_scale_folded: jnp.ndarray,  # f32: (N, m_c, g) | (N, g, m_c); MUST
    v_scale: jnp.ndarray,         #   carry the logit scale pre-folded
    paths: jnp.ndarray,      # (depth, b) i32 — -1 = level unused
    node_lens: jnp.ndarray,  # (N,) i32
    k_dec: jnp.ndarray,      # (b, c_d, g, hd) bf16
    v_dec: jnp.ndarray,
    dec_mask: jnp.ndarray,   # (b, c_d) bool
    *,
    scale: Optional[float] = None,
    block_m: int = 512,
    interpret: Optional[bool] = None,
    ctx_layout: str = "gmk",
) -> jnp.ndarray:
    """Quantized-context twin of ``tree_bifurcated_decode_attention``:
    int8 trie-node segments + per-(token, head) scales (k pre-folded with
    the logit scale), dequantized in-register inside the tree kernel. At
    depth == 1 token-identical to
    ``grouped_bifurcated_decode_attention_q8``."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    scale = hd**-0.5 if scale is None else scale
    if interpret is None:  # static arg: resolved once at trace time
        interpret = jax.default_backend() != "tpu"

    if ctx_layout == "gmk":  # already kernel-major: zero-copy
        kc, vc = k_ctx_q, v_ctx_q
        ks, vs = k_scale_folded, v_scale
    else:
        kc = k_ctx_q.transpose(0, 2, 1, 3)   # (N, g, m_c, hd)
        vc = v_ctx_q.transpose(0, 2, 1, 3)
        ks = k_scale_folded.transpose(0, 2, 1)  # (N, g, m_c)
        vs = v_scale.transpose(0, 2, 1)
    m_c = kc.shape[2]
    qk, path_rows, ctx_bias, kd, vd, bias = _tree_operands(
        q, paths, node_lens, k_dec, v_dec, dec_mask, m_c)
    out = tree_fused_bifurcated_decode_q8(
        qk, kc, vc, ks, vs, path_rows, ctx_bias, kd, vd, bias,
        scale=scale, c_d=c_d, pn=p * n,
        block_m=block_m, interpret=interpret,
    )  # (g, b*p*n, hd), normalized
    out = out.reshape(g, b, p, n, hd).transpose(1, 0, 2, 3, 4)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged dispatchers: page-pool caches, DMA-eliding live-page walk
# ---------------------------------------------------------------------------

def live_page_list(page_tables, seg_lens, page_m: int):
    """Prefix-counted LIVE page list for the paged kernels — pure data.

    page_tables: (N, ppn) i32 pool indices per segment (-1 = unallocated);
    seg_lens: (N,) i32 live token count per segment. A table entry is LIVE
    iff its segment needs it (slot j < ceil(seg_len / page_m)) and it is
    allocated. Returns

      page_ids  (N*ppn,) i32 — live pool pages first, in (segment, page)
                order — the dense kernels' (node, block) stream order,
                which is what makes the paged walk bit-comparable — with
                the tail REPEATING the last live page (revisit ⇒ no DMA);
      page_segs (N*ppn,) i32 — owning segment per entry (same padding);
      n_live    (1,) i32     — live page count (kernel early-exit bound);
      page_bias (N*ppn, page_m) f32 — 0 within the owning segment's live
                length, NEG_INF past it (the ragged-tail mask, per entry).

    Everything is traced jnp — which pages stream is runtime DATA, so the
    decode dispatch never recompiles across admit/retire/readmit.
    """
    n_seg, ppn = page_tables.shape
    page_m = int(page_m)
    needed = -(-seg_lens // page_m)                        # (N,) ceil
    j = jnp.arange(ppn, dtype=jnp.int32)
    live = (j[None, :] < needed[:, None]) & (page_tables >= 0)
    flat_live = live.reshape(-1)
    # stable compaction: live entries first, (segment, page) order kept
    order = jnp.argsort(~flat_live, stable=True)
    ids = jnp.clip(page_tables, 0).reshape(-1)[order]
    segs = jnp.repeat(jnp.arange(n_seg, dtype=jnp.int32), ppn)[order]
    offs = jnp.tile(j * page_m, n_seg)[order]              # token offset
    n_live = jnp.sum(flat_live).astype(jnp.int32)
    last = jnp.maximum(n_live - 1, 0)
    pos = jnp.arange(n_seg * ppn)
    ids = jnp.where(pos < n_live, ids, jnp.take(ids, last)).astype(jnp.int32)
    segs = jnp.where(pos < n_live, segs, jnp.take(segs, last)).astype(jnp.int32)
    offs = jnp.where(pos < n_live, offs, jnp.take(offs, last))
    valid_to = jnp.take(seg_lens, jnp.clip(segs, 0, n_seg - 1))
    cols = offs[:, None] + jnp.arange(page_m)[None, :]
    page_bias = jnp.where(cols < valid_to[:, None], 0.0, NEG_INF
                          ).astype(jnp.float32)
    return ids, segs, n_live[None], page_bias


def paged_walk_grid(*, b, g, p, n, hd, c_d, pm, envelope, depth, itemsize,
                    q8=False):
    """The grid ``paged_bifurcated_decode_attention`` runs for these
    shapes: (kv-head blocks, page-list entries + the decode-arm step). The
    bf16 kernel walks ``walk_head_block`` heads per step, the q8 twin one.
    ``envelope`` is the page-table size N * ppn, ``itemsize`` the pool's."""
    shapes = dict(rows=b * p * n, hd=hd, pm=pm, depth=depth,
                  ld=-(-b * c_d // 128) * 128, itemsize=itemsize)
    hb = 1 if q8 else walk_head_block(g, **shapes)
    return g // hb, envelope + 1


def packed_walk_grid(*, b, g, p, n, hd, c_d, pm, envelope, depth, itemsize,
                     chunk, fresh_tiles, q8=False):
    """The grid of one ``packed_bifurcated_decode_attention`` launch for
    these shapes: ``paged_walk_grid`` with the chunk's ``chunk * p`` query
    rows, the pseudo-segment path level and ``fresh_tiles`` fresh-KV
    entries added."""
    shapes = dict(rows=b * p * n + chunk * p, hd=hd, pm=pm, depth=depth + 1,
                  ld=-(-b * c_d // 128) * 128, itemsize=itemsize, fresh=True)
    hb = 1 if q8 else walk_head_block(g, **shapes)
    return g // hb, envelope + fresh_tiles + 1


def _paged_operands(q, paths, k_dec, v_dec, dec_mask):
    """Shared paged-dispatch plumbing: kernel-major q rows, lane-replicated
    per-level row -> segment assignment, group-major flattened decode arm
    + slot-validity bias (the page list itself comes from
    ``live_page_list``)."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    depth = paths.shape[0]
    qk = q.transpose(1, 0, 2, 3, 4).reshape(g, b * p * n, hd)
    pr = jnp.repeat(paths.astype(jnp.int32), p * n, axis=1)  # (depth, rows)
    path_rows = jnp.broadcast_to(pr[:, :, None], (depth, b * p * n, 128))
    kd = k_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
    vd = v_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
    bias = jnp.where(dec_mask.reshape(1, b * c_d), 0.0, NEG_INF
                     ).astype(jnp.float32)
    return qk, path_rows, kd, vd, bias


@functools.partial(
    jax.jit,
    static_argnames=("scale", "interpret"),
)
def paged_bifurcated_decode_attention(
    q: jnp.ndarray,           # (b, g, p, n, hd) — framework decode layout
    k_pages: jnp.ndarray,     # (P, g, pm, hd) — head-major page pool
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray, # (N, ppn) i32 — pool pages per segment (-1 free)
    seg_lens: jnp.ndarray,    # (N,) i32 — live (ragged) segment lengths
    paths: jnp.ndarray,       # (depth, b) i32 — slot -> segment id per trie
                              #   level, -1 = level unused by that slot
    k_dec: jnp.ndarray,       # (b, c_d, g, hd)
    v_dec: jnp.ndarray,
    dec_mask: jnp.ndarray,    # (b, c_d) bool
    *,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """PAGED fused decode dispatcher — the general form of the whole
    family: single-prefix decoding is one segment with all-zero paths,
    the forest is depth == 1, the trie is the full (depth, b) path table.
    Context KV lives in a shared head-major page pool addressed through
    per-segment page tables; the kernel grid walks a prefix-counted LIVE
    page list (scalar-prefetched), so fully-FREE segments and pages past
    each segment's live length are never DMA'd — the io_model's
    live-length byte envelope becomes the real bytes moved. All paging
    state (pool contents, tables, lengths, paths) is runtime data: one
    compile per (pool, table, slots, depth) shape envelope. The page size
    ``pm`` is the pool's third axis; on fully-populated pages the result
    is bit-identical to the dense kernels at ``block_m == pm``."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    pm = k_pages.shape[2]
    scale = hd**-0.5 if scale is None else scale
    if interpret is None:  # static arg: resolved once at trace time
        interpret = jax.default_backend() != "tpu"

    ids, segs, n_live, page_bias = live_page_list(page_tables, seg_lens, pm)
    qk, path_rows, kd, vd, bias = _paged_operands(
        q, paths, k_dec, v_dec, dec_mask)
    out = paged_fused_bifurcated_decode(
        qk, k_pages, v_pages, ids, segs, n_live, path_rows, page_bias,
        kd, vd, bias,
        scale=scale, c_d=c_d, pn=p * n, interpret=interpret,
    )  # (g, b*p*n, hd), normalized
    out = out.reshape(g, b, p, n, hd).transpose(1, 0, 2, 3, 4)
    return out.astype(q.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "interpret"),
)
def paged_bifurcated_decode_attention_q8(
    q: jnp.ndarray,           # (b, g, p, n, hd) — framework decode layout
    k_pages_q: jnp.ndarray,   # (P, g, pm, hd) int8 — quantized page pool
    v_pages_q: jnp.ndarray,
    k_scale_pages: jnp.ndarray,  # (P, g, pm) f32 — logit scale PRE-FOLDED
    v_scale_pages: jnp.ndarray,  # (P, g, pm) f32
    page_tables: jnp.ndarray, # (N, ppn) i32
    seg_lens: jnp.ndarray,    # (N,) i32
    paths: jnp.ndarray,       # (depth, b) i32 — -1 = level unused
    k_dec: jnp.ndarray,       # (b, c_d, g, hd) bf16
    v_dec: jnp.ndarray,
    dec_mask: jnp.ndarray,    # (b, c_d) bool
    *,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Quantized-context twin of ``paged_bifurcated_decode_attention``:
    int8 pool pages + per-(token, head) f32 scale pages (k pre-folded with
    the logit scale) walked by the same live-page list, dequantized
    in-register. The same CONTRACT as the dense q8 dispatchers applies
    (``scale`` touches the decode arm only)."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    pm = k_pages_q.shape[2]
    scale = hd**-0.5 if scale is None else scale
    if interpret is None:  # static arg: resolved once at trace time
        interpret = jax.default_backend() != "tpu"

    ids, segs, n_live, page_bias = live_page_list(page_tables, seg_lens, pm)
    qk, path_rows, kd, vd, bias = _paged_operands(
        q, paths, k_dec, v_dec, dec_mask)
    out = paged_fused_bifurcated_decode_q8(
        qk, k_pages_q, v_pages_q, k_scale_pages, v_scale_pages,
        ids, segs, n_live, path_rows, page_bias, kd, vd, bias,
        scale=scale, c_d=c_d, pn=p * n, interpret=interpret,
    )  # (g, b*p*n, hd), normalized
    out = out.reshape(g, b, p, n, hd).transpose(1, 0, 2, 3, 4)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Packed dispatchers: one work-queue grid for decode + piggybacked prefill
# ---------------------------------------------------------------------------

def packed_work_queue(page_tables, seg_lens, page_m: int, *,
                      fresh_len, fresh_start, num_fresh_tiles: int,
                      pseudo_seg: int):
    """Work-queue of (kind, seg, page/offset) descriptors — pure data.

    Generalizes ``live_page_list``: the first ``n_live`` entries are the
    live pool pages in the paged kernels' exact (segment, page) stream
    order (which is what keeps decode-only queues bit-comparable), followed
    by ``ceil(fresh_len / page_m)`` FRESH prefill-chunk tiles positioned at
    absolute offset ``fresh_start`` and owned by the ``pseudo_seg`` id that
    only the chunk rows' extra path level carries. Returns

      ent_kind (max_q,) i32 — 0 pool page / 1 fresh tile;
      ent_seg  (max_q,) i32 — owning (pseudo-)segment per entry;
      ent_pdma (max_q,) i32 — pool-page DMA index; fresh/tail entries PIN
               to the last live page (revisit ⇒ no DMA);
      ent_fdma (max_q,) i32 — fresh-tile DMA index; page entries pin
               symmetrically (tile 0 loads once at grid start);
      ent_pos  (max_q,) i32 — absolute position of the entry's column 0
               (pages: 0 — their masking is bias + membership only);
      n_ent    (1,) i32     — live entry count (structural early exit);
      ent_bias (max_q, page_m) f32 — ragged-tail / chunk-length bias.

    ``max_q = page_tables.size + num_fresh_tiles``. Everything is traced
    jnp: chunk lengths, admissions and retirements are runtime DATA, so
    the packed dispatch compiles once per shape envelope. With
    ``fresh_len == 0`` the queue IS the live-page list (zero fresh entries
    enqueued — dead capacity is structurally never streamed).
    """
    pm = int(page_m)
    fcap = int(num_fresh_tiles)
    ids, segs, n_live, page_bias = live_page_list(page_tables, seg_lens, pm)
    max_pages = ids.shape[0]
    max_q = max_pages + fcap
    j = jnp.arange(max_q, dtype=jnp.int32)
    nl = n_live[0]
    fresh_len = jnp.asarray(fresh_len, jnp.int32)
    nf = -(-fresh_len // pm)                        # traced ceil
    is_page = j < nl
    fidx = j - nl
    is_fresh = (~is_page) & (fidx < nf)
    n_ent = (nl + nf).astype(jnp.int32)[None]

    # extend the (max_pages,) page-list arrays to max_q; live_page_list
    # already pins its own tail, so the extension keeps the pin.
    ext = jnp.minimum(j, max_pages - 1)
    ids_x = jnp.take(ids, ext)
    segs_x = jnp.take(segs, ext)
    bias_x = jnp.take(page_bias, ext, axis=0)

    ent_kind = is_fresh.astype(jnp.int32)
    ent_seg = jnp.where(is_fresh, jnp.int32(pseudo_seg), segs_x)
    ent_pdma = ids_x.astype(jnp.int32)              # pinned past n_live
    ent_fdma = jnp.where(
        is_fresh, jnp.clip(fidx, 0, fcap - 1),
        jnp.where(is_page, 0, jnp.clip(nf - 1, 0, fcap - 1)),
    ).astype(jnp.int32)
    ent_pos = jnp.where(
        is_fresh, jnp.asarray(fresh_start, jnp.int32) + fidx * pm, 0
    ).astype(jnp.int32)
    fcols = (jnp.clip(fidx, 0, fcap - 1)[:, None] * pm
             + jnp.arange(pm, dtype=jnp.int32)[None, :])
    fresh_bias = jnp.where(fcols < fresh_len, 0.0, NEG_INF
                           ).astype(jnp.float32)
    ent_bias = jnp.where(is_fresh[:, None], fresh_bias, bias_x)
    return ent_kind, ent_seg, ent_pdma, ent_fdma, ent_pos, n_ent, ent_bias


def _packed_operands(q, paths, k_dec, v_dec, dec_mask,
                     q_fresh, fresh_pos, fresh_path, pseudo_seg):
    """Packed-dispatch plumbing: decode rows ++ chunk rows in one
    kernel-major q, the path table gaining one EXTRA level (pseudo-segment
    for chunk rows, -1 for decode rows), per-row absolute positions for
    the chunk causal mask, and the decode-arm slot-id column (chunk rows:
    -1, so the decode arm contributes exp(NEG_INF - m) == 0 to them)."""
    b, g, p, n, hd = q.shape
    cp = q_fresh.shape[0]
    c_d = k_dec.shape[1]
    depth = paths.shape[0]
    nd = b * p * n
    rows = nd + cp * p
    qk = jnp.concatenate([
        q.transpose(1, 0, 2, 3, 4).reshape(g, nd, hd),
        q_fresh.transpose(1, 0, 2, 3).reshape(g, cp * p, hd).astype(q.dtype),
    ], axis=1)                                       # (g, rows, hd)
    pr = jnp.repeat(paths.astype(jnp.int32), p * n, axis=1)   # (depth, nd)
    dec_path = jnp.concatenate(
        [pr, jnp.full((1, nd), -1, jnp.int32)], axis=0)
    fr = jnp.broadcast_to(
        fresh_path.astype(jnp.int32)[:, None], (depth, cp * p))
    fr_path = jnp.concatenate(
        [fr, jnp.full((1, cp * p), pseudo_seg, jnp.int32)], axis=0)
    path_all = jnp.concatenate([dec_path, fr_path], axis=1)
    path_rows = jnp.broadcast_to(
        path_all[:, :, None], (depth + 1, rows, 128))
    rp = jnp.concatenate([
        jnp.zeros((nd,), jnp.int32),
        jnp.repeat(fresh_pos.astype(jnp.int32), p),
    ])
    row_pos = jnp.broadcast_to(rp[:, None], (rows, 128))
    rs = jnp.concatenate([
        jnp.arange(nd, dtype=jnp.int32) // (p * n),
        jnp.full((cp * p,), -1, jnp.int32),
    ])
    row_slot = jnp.broadcast_to(rs[:, None], (rows, 128))
    kd = k_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
    vd = v_dec.transpose(2, 0, 1, 3).reshape(g, b * c_d, hd)
    bias = jnp.where(dec_mask.reshape(1, b * c_d), 0.0, NEG_INF
                     ).astype(jnp.float32)
    return qk, path_rows, row_pos, row_slot, kd, vd, bias


def _fresh_tiles(k_fresh, v_fresh, pm, g, hd):
    """(F*pm, g, hd) contiguous chunk envelope -> (F, g, pm, hd) tiles."""
    fcap = k_fresh.shape[0] // pm
    kf = k_fresh.reshape(fcap, pm, g, hd).transpose(0, 2, 1, 3)
    vf = v_fresh.reshape(fcap, pm, g, hd).transpose(0, 2, 1, 3)
    return kf, vf, fcap


def _packed_launches(packed_fn, queue, ent_bias, cap, kd_args):
    """Statically split a queue across chained kernel launches of at most
    ``cap`` entries each: every launch but the last flushes raw fp32
    (acc, m, l) partials which seed the next launch's scratch — exact, so
    multi-launch output is bit-identical to single-launch."""
    ent_kind, ent_seg, ent_pdma, ent_fdma, ent_pos, n_ent = queue
    max_q = ent_kind.shape[0]
    n_launch = -(-max_q // cap)
    carry = None
    for t in range(n_launch):
        lo = t * cap
        hi = min(lo + cap, max_q)
        q_t = (ent_kind[lo:hi], ent_seg[lo:hi], ent_pdma[lo:hi],
               ent_fdma[lo:hi], ent_pos[lo:hi],
               jnp.clip(n_ent - lo, 0, hi - lo))
        last = t == n_launch - 1
        res = packed_fn(
            *q_t, ent_bias[lo:hi],
            kd_args if last else (None, None, None),
            carry=carry, emit_partials=not last,
        )
        if last:
            return res
        carry = res


@functools.partial(
    jax.jit,
    static_argnames=("scale", "interpret", "entries_per_launch"),
)
def packed_bifurcated_decode_attention(
    q: jnp.ndarray,           # (b, g, p, n, hd) — framework decode layout
    k_pages: jnp.ndarray,     # (P, g, pm, hd) — head-major page pool
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray, # (N, ppn) i32 — pool pages per segment
    seg_lens: jnp.ndarray,    # (N,) i32
    paths: jnp.ndarray,       # (depth, b) i32 — -1 = level unused
    k_dec: jnp.ndarray,       # (b, c_d, g, hd)
    v_dec: jnp.ndarray,
    dec_mask: jnp.ndarray,    # (b, c_d) bool
    q_fresh: jnp.ndarray = None,   # (cp, g, p, hd) — chunk query rows
    k_fresh: jnp.ndarray = None,   # (F*pm, g, hd) — chunk KV envelope
    v_fresh: jnp.ndarray = None,
    fresh_len: jnp.ndarray = None,   # () i32 — live chunk-KV length
    fresh_start: jnp.ndarray = None, # () i32 — absolute offset of col 0
    fresh_pos: jnp.ndarray = None,   # (cp,) i32 — per-row absolute
                                     #   position, -1 = padded row
    fresh_path: jnp.ndarray = None,  # (depth,) i32 — chunk ancestors
    *,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    entries_per_launch: Optional[int] = None,
):
    """PACKED heterogeneous-step dispatcher: ONE kernel launch processes
    the decode batch's page walk AND a piggybacked suffix-prefill chunk.
    The chunk's query rows join the decode rows on the MXU row dimension,
    its KV arrives as fresh work-queue tiles causally masked per row, and
    its ancestor pages are the SAME pool pages the decode rows stream —
    read once for both (a separate prefill dispatch would read them
    again).

    Returns ``(out_dec (b, g, p, n, hd), out_fresh (cp, g, p, hd))``.
    With no chunk attached (``q_fresh=None``) the queue is decode-only and
    ``out_dec`` is bit-identical to ``paged_bifurcated_decode_attention``.
    ``entries_per_launch`` statically chains multiple launches when the
    queue exceeds one grid envelope (bit-identical to single-launch)."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    pm = k_pages.shape[2]
    n_seg = page_tables.shape[0]
    scale = hd**-0.5 if scale is None else scale
    if interpret is None:  # static arg: resolved once at trace time
        interpret = jax.default_backend() != "tpu"

    depth = paths.shape[0]
    if q_fresh is None:
        q_fresh = jnp.zeros((0, g, p, hd), q.dtype)
        fresh_pos = jnp.zeros((0,), jnp.int32)
    if k_fresh is None:
        k_fresh = jnp.zeros((pm, g, hd), k_dec.dtype)
        v_fresh = jnp.zeros((pm, g, hd), v_dec.dtype)
    if fresh_len is None:
        fresh_len = jnp.int32(0)
    if fresh_start is None:
        fresh_start = jnp.int32(0)
    if fresh_path is None:
        fresh_path = jnp.full((depth,), -1, jnp.int32)
    cp = q_fresh.shape[0]

    kf, vf, fcap = _fresh_tiles(k_fresh, v_fresh, pm, g, hd)
    (ent_kind, ent_seg, ent_pdma, ent_fdma, ent_pos, n_ent,
     ent_bias) = packed_work_queue(
        page_tables, seg_lens, pm,
        fresh_len=fresh_len, fresh_start=fresh_start,
        num_fresh_tiles=fcap, pseudo_seg=n_seg)
    qk, path_rows, row_pos, row_slot, kd, vd, bias = _packed_operands(
        q, paths, k_dec, v_dec, dec_mask,
        q_fresh, fresh_pos, fresh_path, n_seg)

    max_q = ent_kind.shape[0]
    if entries_per_launch is not None and entries_per_launch < max_q:
        def _launch(kind, seg, pdma, fdma, pos, nent, bias_t, kd_args,
                    *, carry, emit_partials):
            kd_t, vd_t, db_t = kd_args
            return packed_fused_bifurcated_decode(
                qk, k_pages, v_pages, kf, vf,
                kind, seg, pdma, fdma, pos, nent,
                path_rows, bias_t, row_pos, row_slot,
                kd_t, vd_t, db_t,
                scale=scale, c_d=c_d, interpret=interpret,
                carry=carry, emit_partials=emit_partials)
        out = _packed_launches(
            _launch, (ent_kind, ent_seg, ent_pdma, ent_fdma, ent_pos,
                      n_ent), ent_bias, entries_per_launch,
            (kd, vd, bias))
    else:
        out = packed_fused_bifurcated_decode(
            qk, k_pages, v_pages, kf, vf,
            ent_kind, ent_seg, ent_pdma, ent_fdma, ent_pos, n_ent,
            path_rows, ent_bias, row_pos, row_slot,
            kd, vd, bias,
            scale=scale, c_d=c_d, interpret=interpret)
    nd = b * p * n
    out_dec = out[:, :nd].reshape(g, b, p, n, hd).transpose(1, 0, 2, 3, 4)
    out_fresh = out[:, nd:].reshape(g, cp, p, hd).transpose(1, 0, 2, 3)
    return out_dec.astype(q.dtype), out_fresh.astype(q.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "interpret", "entries_per_launch"),
)
def packed_bifurcated_decode_attention_q8(
    q: jnp.ndarray,           # (b, g, p, n, hd) — framework decode layout
    k_pages_q: jnp.ndarray,   # (P, g, pm, hd) int8 — quantized page pool
    v_pages_q: jnp.ndarray,
    k_scale_pages: jnp.ndarray,  # (P, g, pm) f32 — logit scale PRE-FOLDED
    v_scale_pages: jnp.ndarray,  # (P, g, pm) f32
    page_tables: jnp.ndarray, # (N, ppn) i32
    seg_lens: jnp.ndarray,    # (N,) i32
    paths: jnp.ndarray,       # (depth, b) i32
    k_dec: jnp.ndarray,       # (b, c_d, g, hd) bf16
    v_dec: jnp.ndarray,
    dec_mask: jnp.ndarray,    # (b, c_d) bool
    q_fresh: jnp.ndarray = None,   # (cp, g, p, hd)
    k_fresh: jnp.ndarray = None,   # (F*pm, g, hd) bf16 — chunk KV stays
    v_fresh: jnp.ndarray = None,   #   full precision until node write
    fresh_len: jnp.ndarray = None,
    fresh_start: jnp.ndarray = None,
    fresh_pos: jnp.ndarray = None,
    fresh_path: jnp.ndarray = None,
    *,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    entries_per_launch: Optional[int] = None,
):
    """Quantized-context twin of ``packed_bifurcated_decode_attention``:
    int8 pool pages + bf16 fresh chunk tiles on one work-queue grid. The
    per-entry scale select keeps decode-only queues bit-identical to
    ``paged_bifurcated_decode_attention_q8``."""
    b, g, p, n, hd = q.shape
    c_d = k_dec.shape[1]
    pm = k_pages_q.shape[2]
    n_seg = page_tables.shape[0]
    scale = hd**-0.5 if scale is None else scale
    if interpret is None:  # static arg: resolved once at trace time
        interpret = jax.default_backend() != "tpu"

    depth = paths.shape[0]
    if q_fresh is None:
        q_fresh = jnp.zeros((0, g, p, hd), q.dtype)
        fresh_pos = jnp.zeros((0,), jnp.int32)
    if k_fresh is None:
        k_fresh = jnp.zeros((pm, g, hd), k_dec.dtype)
        v_fresh = jnp.zeros((pm, g, hd), v_dec.dtype)
    if fresh_len is None:
        fresh_len = jnp.int32(0)
    if fresh_start is None:
        fresh_start = jnp.int32(0)
    if fresh_path is None:
        fresh_path = jnp.full((depth,), -1, jnp.int32)
    cp = q_fresh.shape[0]

    kf, vf, fcap = _fresh_tiles(k_fresh, v_fresh, pm, g, hd)
    (ent_kind, ent_seg, ent_pdma, ent_fdma, ent_pos, n_ent,
     ent_bias) = packed_work_queue(
        page_tables, seg_lens, pm,
        fresh_len=fresh_len, fresh_start=fresh_start,
        num_fresh_tiles=fcap, pseudo_seg=n_seg)
    qk, path_rows, row_pos, row_slot, kd, vd, bias = _packed_operands(
        q, paths, k_dec, v_dec, dec_mask,
        q_fresh, fresh_pos, fresh_path, n_seg)

    max_q = ent_kind.shape[0]
    if entries_per_launch is not None and entries_per_launch < max_q:
        def _launch(kind, seg, pdma, fdma, pos, nent, bias_t, kd_args,
                    *, carry, emit_partials):
            kd_t, vd_t, db_t = kd_args
            return packed_fused_bifurcated_decode_q8(
                qk, k_pages_q, v_pages_q, k_scale_pages, v_scale_pages,
                kf, vf, kind, seg, pdma, fdma, pos, nent,
                path_rows, bias_t, row_pos, row_slot,
                kd_t, vd_t, db_t,
                scale=scale, c_d=c_d, interpret=interpret,
                carry=carry, emit_partials=emit_partials)
        out = _packed_launches(
            _launch, (ent_kind, ent_seg, ent_pdma, ent_fdma, ent_pos,
                      n_ent), ent_bias, entries_per_launch,
            (kd, vd, bias))
    else:
        out = packed_fused_bifurcated_decode_q8(
            qk, k_pages_q, v_pages_q, k_scale_pages, v_scale_pages,
            kf, vf, ent_kind, ent_seg, ent_pdma, ent_fdma, ent_pos, n_ent,
            path_rows, ent_bias, row_pos, row_slot,
            kd, vd, bias,
            scale=scale, c_d=c_d, interpret=interpret)
    nd = b * p * n
    out_dec = out[:, :nd].reshape(g, b, p, n, hd).transpose(1, 0, 2, 3, 4)
    out_fresh = out[:, nd:].reshape(g, cp, p, hd).transpose(1, 0, 2, 3)
    return out_dec.astype(q.dtype), out_fresh.astype(q.dtype)
