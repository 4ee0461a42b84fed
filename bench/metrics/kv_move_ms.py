"""Host time spent moving KV between instances (the program's
``serve.move`` trace span, reactive and planned moves), per decode step
of all engines, over the window (ms)."""
from harness.record import RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    s = run.program_delta("trace.serve.move.s")
    n = run.engine_delta.get("decode_steps", 0)
    if s is None or not n:
        return None
    return 1e3 * s / n
