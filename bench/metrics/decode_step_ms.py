"""Device time of one execution of the paged decode step program, mean
over the traced window (ms)."""
from harness.record import DECODE_STEP, RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    if run.trace is None:
        return None
    ts = run.trace.module_times(DECODE_STEP)
    return 1e3 * sum(ts) / len(ts) if ts else None
