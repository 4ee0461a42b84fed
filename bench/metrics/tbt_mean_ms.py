"""Mean gap between consecutive output tokens of a request, over every
gap that ends in the window, pooled over all requests (ms): the time a
user waits for each streamed token, stalls included."""
from harness.record import RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    gaps = run.gaps_s()
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
