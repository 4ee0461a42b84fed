"""Device time of one execution of the paged prefill chunk program, mean
over the traced window (ms)."""
from harness.record import PREFILL_CHUNK, RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    if run.trace is None:
        return None
    ts = run.trace.module_times(PREFILL_CHUNK)
    return 1e3 * sum(ts) / len(ts) if ts else None
