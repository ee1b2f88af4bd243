"""The benchmark's cost yardstick against the program's own byte model,
and its counts on shapes worked out by hand."""
import pytest

from cost import (Prefill, Sizes, Step, kernel_bytes, kernel_flops,
                  least_time, prefill_bytes, prefill_flops,
                  prefill_model_flops, step_model_flops, tree_decode_io_bytes,
                  window_least_time)
from repro.core import io_model

CASES = [
    dict(paths=[(0,), (0,), (0, 1)], node_lens=[2048, 384], c_d=64, g=8,
         hd=128),
    dict(paths=[(0, 2), (1,), (0, 2)], node_lens=[100, 7, 513], c_d=128,
         g=32, hd=128),
    dict(paths=[(3,)] * 32, node_lens=[0, 0, 0, 8192], c_d=128, g=32, hd=128,
         p=1, n=1),
]


@pytest.mark.parametrize("case", CASES)
def test_tree_bytes_agree_with_the_program(case):
    mine = tree_decode_io_bytes(**case)
    theirs = io_model.tree_decode_io_bytes(**case)
    assert mine["total"] == theirs["total"]
    assert mine["per_node"] == theirs["per_node"]


SIZES = Sizes(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
              d_ff=128, vocab_size=512, act="swiglu")


def test_decode_step_counts():
    # two slots on one 100-token node, decode arms of 3 and 5 tokens
    st = Step({0: 100}, [100, 100], [3, 5])
    kv_tok = 2 * 2 * 16 * 2
    assert kernel_bytes(SIZES, st) == kv_tok * (100 + 8) + 2 * 2 * 4 * 16 * 2
    assert kernel_flops(SIZES, st) == 4 * 4 * 16 * (103 + 105)


def test_prefill_counts():
    # 30 new tokens after 100 cached ones: positions 100..129 attend to
    # 101..130 keys, whatever the chunks
    pf = Prefill(100, 30)
    assert prefill_bytes(SIZES, pf) == 30 * (2 * 2 * 16 + 2 * 4 * 16) * 2
    assert prefill_flops(SIZES, pf) == 4 * 4 * 16 * sum(range(101, 131))
    assert prefill_model_flops(SIZES, pf) == (
        2 * SIZES.matmul_params * 30 + 2 * 64 * 512
        + 2 * prefill_flops(SIZES, pf))


def test_window_least_time_is_the_larger_lower_bound():
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    st = Step({0: 100}, [100, 100], [3, 5])
    # decode alone: the steps' own least times
    assert window_least_time(SIZES, [st, st], [], peaks) == pytest.approx(
        2 * 2 * least_time(SIZES, st, peaks)[0])
    # a long prefill is bound by its operations
    pf = Prefill(4000, 512)
    t = window_least_time(SIZES, [st], [pf], peaks)
    flops = kernel_flops(SIZES, st) + prefill_flops(SIZES, pf)
    assert t == pytest.approx(2 * flops / 197e12)
    assert t >= 2 * least_time(SIZES, st, peaks)[0]


def test_model_flops_and_bound():
    st = Step({0: 100}, [100], [1])
    attn = 64 * 4 * 16 * 2 + 64 * 2 * 16 * 2
    mlp = 3 * 64 * 128
    assert SIZES.matmul_params == 2 * (attn + mlp)
    assert step_model_flops(SIZES, st) == (
        2 * SIZES.matmul_params + 2 * 64 * 512 + 2 * kernel_flops(SIZES, st))
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    t, bound = least_time(SIZES, st, peaks)
    assert bound == "bytes" and t == kernel_bytes(SIZES, st) / 819e9
