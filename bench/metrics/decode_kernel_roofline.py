"""Kernels layer (``kernels/bifurcated_decode.py``): the paged and packed
Pallas decode-attention kernels' share of their roofline: the least time
that the window's decode steps and whole suffix prefills need in attention
(``cost.window_least_time``, from the bytes over the HBM bandwidth and the
operations over the bf16 peak in ``bench/peaks.json``) over the kernels'
device time in the traced window, in percent. The least time is a lower
bound, so the share never reads above what the kernels reach.

A kernel's event is the ``pallas_call`` of ``paged_``/``packed_``
``bifurcated_decode_attention`` (``kernels/ops.py``): on the TPU its op is
named after that function (``packed_bifurcated_decode_attention.13``, read
on a v5e), or its framework path ends in the ``pallas_call``."""
import re

from cost import window_least_time
from trace_reduce import clip, covered_ns

NAME = re.compile(r"(paged|packed)_(bifurcated_decode|fused)")


def is_kernel(name: str, info: str) -> bool:
    return bool(NAME.match(name) or (
        info.rstrip().endswith("pallas_call") and NAME.search(info)))


def read(run):
    if run.trace is None or run.work is None:
        return None
    ops = run.trace.ops
    dev = covered_ns(clip(ops.intervals(ops.where(is_kernel)),
                          *run.trace_window))
    if not dev:
        return None
    least = window_least_time(run.sizes, run.work.steps, run.work.prefills,
                              run.peaks)
    return 100.0 * least / (dev / 1e9) if least else None
