"""80th percentile of time to first token over every request due in the
window, from its due time (ms). A request without a token when the
window closes counts at its wait so far; a failed one as infinite."""
import math

from harness.record import RunRecord, percentile

FAILED_MS = 1e9     # what an infinite percentile is printed as


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    p = percentile(run.ttfts_s(), 80)
    if p is None:
        return None
    return FAILED_MS if math.isinf(p) else 1e3 * p
