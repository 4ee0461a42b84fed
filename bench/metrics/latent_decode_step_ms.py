"""Device time of one execution of the latent family's paged decode
step program (models/mla.py), mean over the traced window (ms)."""
from harness.record import RunRecord

PROGRAM = "_decode_step_latent_jit"


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    if run.trace is None:
        return None
    ts = run.trace.module_times(PROGRAM)
    return 1e3 * sum(ts) / len(ts) if ts else None
