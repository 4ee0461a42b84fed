"""Paged decode attention kernel (kernels/micro_attn_decode.py): least
time of the work it was asked for (the contexts attended, not the table
padding or the grid) over its summed device time (%)."""
from harness.record import DECODE_KERNEL, RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    w = run.window_work()
    return run.roofline(DECODE_KERNEL, w["decode_flops"],
                        w["decode_bytes"])
