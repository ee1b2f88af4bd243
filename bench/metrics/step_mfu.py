"""Model step (``models/transformer.py``): the model operations of the
traced window's decode steps and of the suffix prefills that ran whole in
it (2 per weight per token, the output head, attention over each token's
context; ``bench/cost.py``) over the device's busy time times the chip's
bf16 peak, in percent. It bounds what any change below it can claim."""
from cost import prefill_model_flops, step_model_flops
from trace_reduce import mean_busy_s


def read(run):
    if run.trace is None or not run.work.steps:
        return None
    busy = mean_busy_s(run.trace, *run.trace_window)
    if not busy:
        return None
    flops = (sum(step_model_flops(run.sizes, st) for st in run.work.steps)
             + sum(prefill_model_flops(run.sizes, pf)
                   for pf in run.work.prefills))
    return 100.0 * flops / (busy * run.peaks["bf16_flops"])
