"""Running decode slots over all slots (instances x max_batch), after
every server step in the window, averaged (%)."""
from harness.record import RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    xs = [s.running / s.slots for s in run.samples
          if run.in_window(s.t) and s.slots]
    return 100.0 * sum(xs) / len(xs) if xs else None
