"""The one traffic generator. It reads a mix's data file
(``bench/traffic/<mix>.json``) and yields the requests of a run.

A request is a path of token segments (the shared prefix first, when the
mix has one), a number of samples, a number of new tokens and the second,
counted from the start of the window, at which it is due (open loop:
arrivals do not wait for answers).

Every seed gets the same work at the same times. The sizes of the
requests, their shared prefixes and the gaps between arrivals are drawn
from the mix's own ``shape_seed``; ``--seed`` draws every token id. So two
seeds differ in what the prompts say, and not in how much there is to do
or when. (Near the knee a permutation of the same requests is another
pattern of bursts: runs of different seeds then differed by 15-35 % in
their tails, against a few percent between two runs of one seed.)

Lengths are lognormal around ``median`` with log-sd ``sigma``, clipped to
``[min, max]``. Shared prefixes are picked with Zipf(``zipf_alpha``)
popularity over ``count`` prompts of ``tokens`` tokens.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    segments: List[np.ndarray]   # int32 token arrays, trie path order
    n_samples: int
    max_new_tokens: int
    due: float = 0.0             # seconds after the window opens
    prefix: Optional[int] = None  # which shared prefix, if any

    @property
    def prompt_len(self) -> int:
        return int(sum(len(s) for s in self.segments))


def lognormal_lengths(rng, spec, n: int) -> np.ndarray:
    """``n`` lengths, lognormal around ``spec["median"]``, clipped."""
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_weights(count: int, alpha: float) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1) ** alpha
    return w / w.sum()


class Traffic:
    """Requests of one mix for one seed. ``vocab`` is the model's."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = int(vocab)
        self.seed = int(seed)
        pre = mix.get("prefixes")
        rng = np.random.default_rng([self.seed, 0])
        # the shared prefixes' contents follow the seed; their number and
        # lengths are the mix's
        self.prefixes = ([self._tokens(rng, pre["tokens"])
                          for _ in range(pre["count"])] if pre else [])

    def _tokens(self, rng, n: int) -> np.ndarray:
        return rng.integers(0, self.vocab, size=int(n), dtype=np.int32)

    def _shapes(self, n: int):
        """``n`` request shapes (prefix id, prompt length, new tokens) and
        ``n + 1`` inter-arrival gaps, drawn request by request from the
        mix's ``shape_seed``, so a window of fewer requests sends the first
        of them: every length it sends, a longer window sends too."""
        mix = self.mix
        srng = np.random.default_rng([mix["shape_seed"], 1])
        pre = mix.get("prefixes")
        w = zipf_weights(pre["count"], pre["zipf_alpha"]) if pre else None
        rows = []
        for _ in range(n + 1):
            pid = int(srng.choice(pre["count"], p=w)) if pre else -1
            plen = int(lognormal_lengths(srng, mix["prompt"], 1)[0])
            new = int(lognormal_lengths(srng, mix["new_tokens"], 1)[0])
            rows.append((pid, plen, new, float(srng.exponential(1.0))))
        pid, plen, new, gaps = (np.array(c) for c in zip(*rows))
        return pid[:n], plen[:n], new[:n], gaps

    def _request(self, rng, pid, plen, new) -> Request:
        segs = [] if pid < 0 else [self.prefixes[int(pid)]]
        segs.append(self._tokens(rng, plen))
        return Request(segments=segs, n_samples=int(self.mix["n_samples"]),
                       max_new_tokens=int(new),
                       prefix=None if pid < 0 else int(pid))

    def window(self, seconds: float) -> List[Request]:
        """The window's requests: ``rate_per_s * seconds`` of them, due at
        Poisson arrival times scaled to fall inside the window."""
        n = max(1, int(round(self.mix["rate_per_s"] * seconds)))
        pid, plen, new, gaps = self._shapes(n)
        rng = np.random.default_rng([self.seed, 1, n])
        reqs = [self._request(rng, pid[i], plen[i], new[i]) for i in range(n)]
        due = np.cumsum(gaps)[:n] / gaps.sum() * seconds
        for r, t in zip(reqs, due):
            r.due = float(t)
        return reqs

    def warmup(self, window: List[Request]) -> List[Request]:
        """The mix's own traffic before the window, from a stream apart from
        it: one request for each prompt length that ``window`` sends, the
        first ones on each shared prefix in turn, every one with the mix's
        fewest new tokens. It leaves every prefix resident and the pool as
        a running server's, and it makes the program build whatever it
        builds per prompt length before the window opens."""
        rng = np.random.default_rng([self.seed, 2])
        lengths = sorted({len(r.segments[-1]) for r in window})
        new = int(self.mix["new_tokens"]["min"])
        n = max(len(lengths), len(self.prefixes))
        return [self._request(rng, i % len(self.prefixes)
                              if self.prefixes else -1,
                              lengths[i % len(lengths)], new)
                for i in range(n)]
