"""Share of the traced time with a request in the system in which the
chip ran no program (%)."""
from harness.record import RunRecord
from harness.trace import total


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    if run.trace is None:
        return None
    within = run.in_system()
    span = total([(max(s, run.trace.window[0]), min(e, run.trace.window[1]))
                  for s, e in within if e > run.trace.window[0]
                  and s < run.trace.window[1]])
    if span <= 0:
        return None
    busy = total(run.trace.busy(0, within))
    return 100.0 * (1.0 - busy / span)
