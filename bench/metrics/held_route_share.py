"""Top-k (token, expert) assignments of the window's decode steps that
landed on the experts this instance holds, over all of them (the
program's ``count.moe.held`` and ``count.moe.routed``) (%). With a
share of E_held of E experts it should read near E_held / E."""
from harness.record import RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    routed = run.program_delta("count.moe.routed")
    if not routed:
        return None
    return 100.0 * run.program_delta("count.moe.held") / routed
