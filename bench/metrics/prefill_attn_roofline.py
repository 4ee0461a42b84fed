"""Paged prefill attention kernel (kernels/micro_attn_prefill.py): least
time of the work it was asked for (each chunk's queries over the prefix
already written) over its summed device time (%)."""
from harness.record import PREFILL_KERNEL, RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    w = run.window_work()
    return run.roofline(PREFILL_KERNEL, w["prefill_flops"],
                        w["prefill_bytes"])
