"""The check's control: the plain reference with int8 weights and
activations (the precision below the bfloat16 the program serves in), put
in the program's place and judged by the verdict that decides ``correct``
(``harness.verdict``, with the cell's limit). On the chip, at the cell's
own size, ``bench/calibrate.py`` does the same on the window's own served
tokens (PERF.md, section 2). The CPU holds internlm2-1.8b's published
widths cut to six layers, with a 256-token prompt and 384 served tokens
(the reference's own greedy choices, teacher-forced): there the control's
mean logit gap fails the verdict, the reference with its weights rounded
to bfloat16 (the program's precision) passes it, and the control's mean
gap is more than three times the latter's."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness as H
from conftest import BENCH

SEEDS = (1, 2, 3)
LAYERS, PROMPT, SERVED = 6, 256, 384


@pytest.fixture(scope="module")
def setup():
    from repro.models import get_model

    with open(os.path.join(BENCH, "configs", "internlm2.json")) as f:
        config = json.load(f)
    cfg = dataclasses.replace(H.model_config(config), n_layers=LAYERS)
    sizes = dict(config["model"], n_layers=LAYERS)
    cell = H.make_cell("internlm2.sysprompt_zipf", "internlm2",
                       "sysprompt_zipf")
    return get_model(cfg), cfg, sizes, H.load_reference(config), cell


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_control_fails_the_check(setup, seed):
    model, cfg, sizes, ref_mod, cell = setup
    params = H.make_weights(model, seed)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, PROMPT).astype(np.int32)
    ref = ref_mod.Reference(sizes, PROMPT + SERVED - 1, SERVED)
    # served tokens: the reference's own greedy choices, teacher-forced
    served = rng.integers(0, cfg.vocab_size, SERVED).astype(np.int32)
    served = ref.logits(params, prompt, served).argmax(-1).astype(np.int32)
    lg = ref.logits(params, prompt, served)
    control = ref_mod.control_gaps(
        lg, ref.logits(params, prompt, served, control=True)).mean()
    bf16 = jax.tree.map(lambda w: w.astype(jnp.bfloat16).astype(w.dtype),
                        params)
    program = ref_mod.control_gaps(
        lg, ref.logits(bf16, prompt, served)).mean()
    assert H.verdict(cell, program, SERVED), program
    assert not H.verdict(cell, control, SERVED), control
    assert control > 3 * program, (control, program)
