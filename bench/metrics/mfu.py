"""Model operations of the window's work (prompts prefilled, tokens
decoded) over the window's length times the chip's bf16 peak (%): the
whole step's share of the peak."""
from harness.record import RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    flops = run.window_work()["model_flops"]
    if flops <= 0:
        return None
    w0, w1 = run.window
    return 100.0 * flops / ((w1 - w0) * run.chips
                            * run.peaks["bf16_flops_per_s"])
