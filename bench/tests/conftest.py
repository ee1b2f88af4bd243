"""Shared set-up of the benchmark's own tests: run them on the CPU with

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest bench/tests
"""
import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import pytest  # noqa: E402


def tiny_cell(workload: str):
    """The cell ``<config>.<mix>`` from its files, with its traffic scaled
    down to a size the CPU runs in seconds (interpret-mode kernels) and its
    model cut to ``reduced_config`` widths. Returns (cell, model config)."""
    import harness as H
    from repro.configs import get_config, reduced_config

    cell = H.make_cell(workload, *workload.split("."))
    mix = copy.deepcopy(cell.mix)
    if mix.get("prefixes"):
        mix["prefixes"]["tokens"] = 128
    p = mix["prompt"]
    mix["prompt"] = dict(p, median=min(48, p["median"]), min=min(8, p["min"]),
                         max=min(96, p["max"]))
    mix["new_tokens"] = dict(mix["new_tokens"],
                             median=min(6, mix["new_tokens"]["median"]),
                             min=min(3, mix["new_tokens"]["min"]),
                             max=min(10, mix["new_tokens"]["max"]))
    mix["n_samples"] = min(4, mix["n_samples"])
    mix["engine"].update(slots=min(16, mix["engine"]["slots"]),
                         n_nodes=min(12, mix["engine"]["n_nodes"]),
                         node_capacity=128, decode_capacity=16,
                         page_size=16, num_pages=64)
    mix["rate_per_s"] = 4.0
    mix["check"]["requests"] = 4
    cell.mix = mix
    return cell, reduced_config(get_config(cell.config["arch"]))


@pytest.fixture
def peaks():
    import harness as H
    return H.load_peaks("TPU v5 lite")
