"""The traffic generator: deterministic from its seed, the same work for
every seed, and the distributions each mix states."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from loadgen import Traffic, lognormal_lengths, zipf_weights

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
               if f.endswith(".json"))


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def flat(reqs):
    return [(r.due, r.n_samples, r.max_new_tokens,
             tuple(np.concatenate(r.segments).tolist())) for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    m = mix(name)
    a = Traffic(m, 1000, 2**31 + 12345).window(5.0)
    b = Traffic(m, 1000, 2**31 + 12345).window(5.0)
    assert flat(a) == flat(b)
    assert flat(Traffic(m, 1000, 2**31 + 12346).window(5.0)) != flat(a)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    m = mix(name)
    shapes = []
    for seed in (1, 2**32 + 7):
        reqs = Traffic(m, 1000, seed).window(20.0)
        # the same requests, due at the same times, in the same order
        shapes.append([(r.due, r.prompt_len, r.max_new_tokens, r.prefix)
                       for r in reqs])
        gaps = np.diff([0.0] + [r.due for r in reqs])
        assert len(reqs) == round(m["rate_per_s"] * 20.0)
        assert np.all(gaps >= 0) and reqs[-1].due < 20.0
    assert shapes[0] == shapes[1]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_stated_distribution(name):
    m = mix(name)
    rng = np.random.default_rng(0)
    for key in ("prompt", "new_tokens"):
        spec = m[key]
        x = lognormal_lengths(rng, spec, 20000)
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert abs(np.median(x) - spec["median"]) <= 0.05 * spec["median"] + 1


def test_zipf_popularity_of_shared_prefixes():
    m = mix("sysprompt_zipf")
    n = 4000
    m["rate_per_s"] = n / 100.0
    reqs = Traffic(m, 1000, 5).window(100.0)
    counts = np.bincount([r.prefix for r in reqs],
                         minlength=m["prefixes"]["count"]) / len(reqs)
    w = zipf_weights(m["prefixes"]["count"], m["prefixes"]["zipf_alpha"])
    assert np.allclose(counts, w, atol=0.02)
    # a prefix's tokens are the same in every request that uses it
    by = {}
    for r in reqs:
        by.setdefault(r.prefix, set()).add(r.segments[0].tobytes())
    assert all(len(v) == 1 for v in by.values())
    assert all(len(r.segments[0]) == m["prefixes"]["tokens"] for r in reqs)


def test_arrivals_are_poisson():
    m = mix("sysprompt_zipf")
    m["rate_per_s"] = 50.0
    reqs = Traffic(m, 1000, 9).window(200.0)
    gaps = np.diff([r.due for r in reqs])
    # exponential gaps: mean 1/rate, coefficient of variation 1
    assert abs(gaps.mean() * 50.0 - 1.0) < 0.05
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1


def test_warmup_covers_every_prefix_and_prompt_length_apart_from_the_window():
    m = mix("sysprompt_zipf")
    t = Traffic(m, 1000, 3)
    window = t.window(51.0)
    warm = t.warmup(window)
    assert {r.prefix for r in warm} == set(range(m["prefixes"]["count"]))
    assert ({len(r.segments[-1]) for r in warm}
            == {len(r.segments[-1]) for r in window})
    assert all(r.max_new_tokens == m["new_tokens"]["min"] for r in warm)
    win = {tuple(r.segments[-1].tolist()) for r in window}
    assert not win & {tuple(r.segments[-1].tolist()) for r in warm}
    # the same for every seed but in what the prompts say
    other = Traffic(m, 1000, 2**31 + 99).warmup(window)
    assert [(r.prefix, r.prompt_len) for r in other] == \
        [(r.prefix, r.prompt_len) for r in warm]


def test_a_shorter_window_sends_lengths_a_longer_one_sends():
    m = mix("sysprompt_zipf")
    short = Traffic(m, 1000, 11).window(20.0)
    long = Traffic(m, 1000, 2**31 + 3).window(60.0)
    assert ({(r.prompt_len, r.max_new_tokens) for r in short}
            <= {(r.prompt_len, r.max_new_tokens) for r in long})
