"""Host time the engines spend building a decode step's tables and
inputs (CommStats.host_gather_s), per decode step, over the window (ms)."""
from harness.record import RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    n = run.engine_delta.get("decode_steps", 0)
    if not n:
        return None
    return 1e3 * run.engine_delta["host_gather_s"] / n
