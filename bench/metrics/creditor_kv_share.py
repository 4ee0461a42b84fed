"""KV tokens of running requests held in a creditor's pool, over all
their KV tokens, after every server step in the window, averaged (%)."""
from harness.record import RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    xs = [s.creditor_tokens / s.kv_tokens for s in run.samples
          if run.in_window(s.t) and s.kv_tokens]
    return 100.0 * sum(xs) / len(xs) if xs else None
