"""Operations and bytes that the serving path's work needs, computed from
shapes. The yardstick behind ``decode_kernel_roofline`` and ``step_mfu``:
kept with the benchmark so that no change to the program can change it.

Counts are what the algorithm needs, not what a kernel happens to move:
each context token that some row attends to is read once per kv head, each
live decode-arm token once, queries and outputs once; a kernel that streams
more (padding, unreferenced pages, the whole decode capacity) spends more
time than this least time, and its roofline share says so.

``tree_decode_io_bytes`` is a copy of the byte count in the program's
``core/io_model.py`` (the "tree" accounting); ``bench/tests`` checks that
the two agree.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Sequence, Tuple


def tree_decode_io_bytes(*, paths: Sequence[Sequence[int]], node_lens,
                         c_d: int, g: int, hd: int, p: int = 1, n: int = 1,
                         bytes_per_el: int = 2) -> Dict[str, object]:
    """Bytes of one trie decode step for one layer: every node that a slot
    references read once (K and V), each slot's decode arm of ``c_d``
    tokens, and the query and output rows."""
    used = sorted({nid for pth in paths for nid in pth})
    per_node = {nid: 2 * g * int(node_lens[nid]) * hd * bytes_per_el
                for nid in used}
    rows = len(paths) * p * n
    dec = 2 * g * len(paths) * c_d * hd * bytes_per_el
    q_io = rows * g * hd * bytes_per_el
    out_io = rows * g * hd * bytes_per_el
    return {"per_node": per_node,
            "total": sum(per_node.values()) + dec + q_io + out_io}


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    act: str

    @classmethod
    def from_model(cls, model: dict) -> "Sizes":
        return cls(**{f.name: model[f.name] for f in dataclasses.fields(cls)})

    @property
    def matmul_params(self) -> int:
        """Weights of every matrix product a token goes through, output
        head included, embedding lookup excluded."""
        d, h, hd = self.d_model, self.n_heads, self.head_dim
        g = self.n_kv_heads
        attn = d * h * hd * 2 + d * g * hd * 2
        mlp = (3 if self.act in ("swiglu", "geglu") else 2) * d * self.d_ff
        return self.n_layers * (attn + mlp)

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab_size


@dataclasses.dataclass
class Step:
    """What one decode step of the engine works on, as the host saw it.

    ``ctx_nodes``: {node id: live tokens} of every trie node that some row
    attends to; ``slot_ctx``: each active slot's context length;
    ``slot_dec``: each active slot's decode-arm tokens after the step's
    write."""

    ctx_nodes: Dict[int, int]
    slot_ctx: List[int]
    slot_dec: List[int]


@dataclasses.dataclass
class Prefill:
    """The suffix prefill of one admitted request: ``tokens`` prompt tokens
    computed after ``before`` tokens found in the prefix cache. The packed
    step runs it in chunks beside the decode rows."""

    before: int
    tokens: int


def kernel_bytes(s: Sizes, step: Step, bytes_per_el: int = 2) -> int:
    """HBM bytes one decode-attention layer-step needs."""
    g, h, hd = s.n_kv_heads, s.n_heads, s.head_dim
    kv_tok = 2 * g * hd * bytes_per_el
    total = kv_tok * sum(step.ctx_nodes.values())
    total += kv_tok * sum(step.slot_dec)
    total += 2 * len(step.slot_ctx) * h * hd * bytes_per_el  # q in, out out
    return total


def kernel_flops(s: Sizes, step: Step) -> int:
    """Operations of one decode-attention layer-step: q.k and p.v over
    every (row, key) pair."""
    keys = sum(c + d for c, d in zip(step.slot_ctx, step.slot_dec))
    return 4 * s.n_heads * s.head_dim * keys


def prefill_bytes(s: Sizes, pf: Prefill, bytes_per_el: int = 2) -> int:
    """HBM bytes one layer of a suffix prefill needs in attention: the new
    tokens' K/V and their query and output rows. The cached tokens before
    them are left out: other rows of the same steps read them too."""
    g, h, hd = s.n_kv_heads, s.n_heads, s.head_dim
    return pf.tokens * (2 * g * hd + 2 * h * hd) * bytes_per_el


def prefill_flops(s: Sizes, pf: Prefill) -> int:
    """Operations of one layer of a suffix prefill in attention: the token
    at position p attends to p + 1 keys, however the prefill is chunked."""
    lo, hi = pf.before, pf.before + pf.tokens
    keys = (hi * (hi + 1) - lo * (lo + 1)) // 2
    return 4 * s.n_heads * s.head_dim * keys


def least_time(s: Sizes, step: Step, peaks: dict) -> Tuple[float, str]:
    """Least seconds one decode layer-step can take on the chip, and which
    bound sets it ("bytes" or "flops")."""
    tb = kernel_bytes(s, step) / peaks["hbm_bytes_per_s"]
    tf = kernel_flops(s, step) / peaks["bf16_flops"]
    return (tb, "bytes") if tb >= tf else (tf, "flops")


def window_least_time(s: Sizes, steps: Iterable[Step],
                      prefills: Iterable[Prefill], peaks: dict) -> float:
    """Least seconds the attention kernels need, over every layer, for the
    decode steps and suffix prefills of a window. The host does not see
    which step ran which prefill chunk, so this is the larger of two lower
    bounds: the decode steps' least times summed step by step, and all the
    work's bytes over the bandwidth or its operations over the peak,
    whichever is larger."""
    steps, prefills = list(steps), list(prefills)
    per_step = sum(least_time(s, st, peaks)[0] for st in steps)
    nbytes = (sum(kernel_bytes(s, st) for st in steps)
              + sum(prefill_bytes(s, pf) for pf in prefills))
    flops = (sum(kernel_flops(s, st) for st in steps)
             + sum(prefill_flops(s, pf) for pf in prefills))
    whole = max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])
    return s.n_layers * max(per_step, whole)


def step_model_flops(s: Sizes, step: Step) -> int:
    """Model operations of one decode step: 2 per weight per decode row, the
    output head for each row, and attention in every layer."""
    rows = len(step.slot_ctx)
    return (2 * (s.matmul_params + s.head_params) * rows
            + s.n_layers * kernel_flops(s, step))


def prefill_model_flops(s: Sizes, pf: Prefill) -> int:
    """Model operations of one suffix prefill: 2 per weight per token, the
    output head for its last token, and attention in every layer."""
    return (2 * s.matmul_params * pf.tokens + 2 * s.head_params
            + s.n_layers * prefill_flops(s, pf))
