"""80th percentile of queue wait over every request due in the window:
from its due time (the origin ``ttft_p80_ms`` uses) to when an engine
began admitting it (ms). A request not admitted when the window closes
counts at its wait so far; one that failed unadmitted as infinite."""
import math

from harness.record import RunRecord, percentile

FAILED_MS = 1e9     # what an infinite percentile is printed as


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    w1 = run.window[1]
    waits = []
    for r in run.records:
        if not run.in_window(r.due):
            continue
        if r.admitted_at is not None and r.admitted_at < w1:
            waits.append(r.admitted_at - r.due)
        elif r.state == "FAILED" and r.admitted_at is None:
            waits.append(float("inf"))
        else:
            waits.append(w1 - r.due)
    p = percentile(waits, 80)
    if p is None:
        return None
    return FAILED_MS if math.isinf(p) else 1e3 * p
