"""Paged latent decode kernel (kernels/latent_decode.py): least time of
the work it was asked for (the contexts attended, from the
architecture's ``decode_attn_work``; not the table padding, the grid or
the rows' lane padding) over its summed device time (%)."""
from harness.record import RunRecord

KERNEL = "paged_latent_attention"


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    w = run.window_work()
    return run.roofline(KERNEL, w["decode_flops"], w["decode_bytes"])
