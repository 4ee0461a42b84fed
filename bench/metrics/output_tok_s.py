"""Output tokens emitted in the window, over the window's length."""
from harness.record import RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    w0, w1 = run.window
    return len(run.window_tokens()) / (w1 - w0)
