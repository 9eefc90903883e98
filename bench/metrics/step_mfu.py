"""Model step: useful model FLOPs of the chunks drained inside the window
over the window's seconds times the chip's bf16 peak (%).

Useful means the tokens served: prompt rows that were not prefix hits,
and one row per generated token, each at its live context, with the LM
head only for the rows whose next token is sampled (``work.py``).  The
padding rows of the fused chunk are not counted."""

from work import step_flops


def read(run):
    steps = [] if run.replay is None else run.replay.steps(run.w0, run.w1)
    if not steps:
        return None
    flops = sum(step_flops(run.model, s) for s in steps)
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"])
