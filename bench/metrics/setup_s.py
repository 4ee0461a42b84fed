"""Process start to the window's start: weights, compilation or the
compile cache, warm-up traffic."""
from harness.record import RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    return run.setup_s
