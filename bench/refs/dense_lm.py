"""Plain reference of a dense decoder-only transformer, for the check that
decides ``correct``.

Straightforward ``jax.numpy``: token embedding, then per layer RMSNorm,
query/key/value projections, rotary positions (half rotation), causal
softmax attention with grouped kv heads, the output projection, a residual,
RMSNorm and the MLP (SwiGLU, or GELU with the tanh approximation), a
residual; a final RMSNorm and the output head. No cache, no kernels, no
batching. It imports nothing of the program: it reads the sizes from the
configuration's file and the weights that the benchmark made from the seed,
named as the program names them (``embed``, ``layers/attn/wq`` ...).

``forward`` runs at float32 with matmul precision "highest". ``matmul``
swaps the linear layers' product: ``int8_matmul`` is the control, the
same network with int8 weights and activations (per-channel and per-row
symmetric scales), the precision below the bfloat16 that the program
serves in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def f32_matmul(x, w):
    return jnp.dot(x, w, precision=lax.Precision.HIGHEST)


def int8_matmul(x, w):
    """x @ w with both sides quantized to int8: w per output column, x per
    row, accumulated in int32."""
    sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    sw = jnp.where(sw > 0, sw, 1.0)
    sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    sx = jnp.where(sx > 0, sx, 1.0)
    wq = jnp.clip(jnp.rint(w / sw), -127, 127).astype(jnp.int8)
    xq = jnp.clip(jnp.rint(x / sx), -127, 127).astype(jnp.int8)
    acc = lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (n, heads, hd) rotated at positions ``pos`` (n,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, q_block):
    """Causal attention. q (n, h, hd), k/v (n, g, hd); query blocks of
    ``q_block`` rows so that (h, q_block, n) scores are the largest
    buffer."""
    n, h, hd = q.shape
    g = k.shape[1]
    p = h // g
    nb = n // q_block
    qb = q.reshape(nb, q_block, g, p, hd)
    kpos = jnp.arange(n)

    def block(_, inp):
        qi, i = inp
        s = jnp.einsum("cgpk,mgk->gpcm", qi, k,
                       precision=lax.Precision.HIGHEST) * hd ** -0.5
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("gpcm,mgk->cgpk", w, v,
                       precision=lax.Precision.HIGHEST)
        return None, o.reshape(q_block, h, hd)

    _, out = lax.scan(block, None, (qb, jnp.arange(nb)))
    return out.reshape(n, h, hd)


def _layer(sizes, mm, q_block, x, lw):
    n = x.shape[0]
    h, g, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    pos = jnp.arange(n)
    a = lw["attn"]
    y = rms_norm(x, lw["ln1"]["scale"], eps)
    q = mm(y, a["wq"]).reshape(n, -1, hd)[:, :h]
    k = mm(y, a["wk"]).reshape(n, -1, hd)[:, :g]
    v = mm(y, a["wv"]).reshape(n, -1, hd)[:, :g]
    o = attention(rope(q, pos, theta), rope(k, pos, theta), v, q_block)
    x = x + mm(o.reshape(n, h * hd), a["wo"][:h * hd])
    y = rms_norm(x, lw["ln2"]["scale"], eps)
    m = lw["mlp"]
    if sizes["act"] == "swiglu":
        hid = jax.nn.silu(mm(y, m["wi_gate"])) * mm(y, m["wi_up"])
    elif sizes["act"] == "gelu":
        hid = jax.nn.gelu(mm(y, m["wi"]), approximate=True)
    else:
        raise ValueError(f"reference has no activation {sizes['act']!r}")
    return x + mm(hid, m["w_down"]), None


@functools.partial(jax.jit, static_argnames=("sizes_key", "n_out", "q_block",
                                             "control"))
def _logits(weights, tokens, start, *, sizes_key, n_out, q_block, control):
    sizes = dict(sizes_key)
    mm = int8_matmul if control else f32_matmul
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    x, _ = lax.scan(functools.partial(_layer, sizes, mm, q_block), x,
                    weights["layers"])
    x = lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    x = rms_norm(x, weights["final_norm"]["scale"], sizes["norm_eps"])
    head = weights["lm_head"] if "lm_head" in weights else weights["embed"]
    head = head[:sizes["vocab_size"]]
    return mm(x, head.T)


class Reference:
    """Logits of the reference at the positions that predict a request's
    served tokens. Sequences are padded to ``seq_len`` (a multiple of
    ``q_block``) so that one compiled program serves every request of a
    cell: the causal mask keeps padding out of every position read."""

    def __init__(self, sizes: dict, seq_len: int, n_out: int,
                 q_block: int = 512):
        keys = ("n_heads", "n_kv_heads", "head_dim", "norm_eps", "rope_theta",
                "act", "vocab_size")
        self.sizes_key = tuple((k, sizes[k]) for k in keys)
        self.q_block = q_block
        self.seq_len = -(-seq_len // q_block) * q_block
        self.n_out = n_out

    def logits(self, weights, prompt, served, *, control=False):
        """(len(served), vocab) float32 logits: row i is the reference's
        prediction of ``served[i]`` after ``prompt ++ served[:i]``."""
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        if len(seq) > self.seq_len or len(served) > self.n_out:
            raise ValueError(f"sequence of {len(seq)} (+{len(served)}) "
                             f"exceeds the reference's {self.seq_len} "
                             f"(+{self.n_out})")
        tok = np.zeros(self.seq_len, np.int32)
        tok[:len(seq)] = seq
        start = min(len(prompt) - 1, self.seq_len - self.n_out)
        out = _logits(weights, jnp.asarray(tok), jnp.int32(start),
                      sizes_key=self.sizes_key, n_out=self.n_out,
                      q_block=self.q_block, control=control)
        off = len(prompt) - 1 - start
        return np.asarray(out)[off:off + len(served)]


def served_gaps(ref_logits, tokens) -> np.ndarray:
    """For each served token, the gap by which its reference logit lies
    below the reference's best at its position (0 where they agree)."""
    ref_logits = np.asarray(ref_logits, np.float64)
    tokens = np.asarray(tokens)
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]


def logprobs(logits, tokens) -> np.ndarray:
    """Log-probabilities of ``tokens`` under ``logits``, row by row."""
    x = np.asarray(logits, np.float64)
    m = x.max(axis=-1, keepdims=True)
    lse = m[:, 0] + np.log(np.exp(x - m).sum(axis=-1))
    return x[np.arange(len(tokens)), np.asarray(tokens)] - lse


def logprob_error(ref_logits, tokens, got) -> float:
    """Largest |log p(token)| difference between ``got`` and the
    reference, over the served tokens."""
    return float(np.max(np.abs(logprobs(ref_logits, tokens)
                               - np.asarray(got, np.float64))))


def control_gaps(ref_logits, ctl_logits) -> np.ndarray:
    """The same gaps for the tokens that the control puts first."""
    return served_gaps(ref_logits, np.asarray(ctl_logits).argmax(axis=-1))
