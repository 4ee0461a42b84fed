"""Host time of one frontend step of the server (the program's
``serve.step`` trace span: dispatch, heartbeats, planning, moves and
every engine's step, device waits included), per step, over the window
(ms)."""
from harness.record import RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    n = run.program_delta("trace.serve.step.n")
    if not n:
        return None
    return 1e3 * run.program_delta("trace.serve.step.s") / n
