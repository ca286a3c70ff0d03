"""Whole step's model FLOP utilization, percent: model operations per step
(``bench/work/model_flops.py``) over the traced window's time per step, over
the chips' bf16 peak."""

from bench import spec


def read(run):
    if not run.steps or run.window_s <= 0:
        return None
    ops = spec.work_counter("model_flops").count(run.cfg, run.global_batch, run.seq)
    step_s = run.window_s / run.steps
    return 100.0 * ops / step_s / (run.chips * run.peaks["bf16_flops_per_s"])
