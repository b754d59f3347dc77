"""Whole step: the model FLOPs the traced steps needed (valid tokens and
valid attention pairs, ``bench.flops.step_flops``) over the traced window
times the chips' bf16 peak, in %."""
from bench import flops


def read(run):
    t = run.trace
    if t is None or not run.trace_steps or t.window_s <= 0:
        return None
    need = sum(flops.step_flops(run.config, s) for s in run.trace_steps)
    return 100.0 * need / (t.window_s * run.chips * run.peaks.bf16_flops)
