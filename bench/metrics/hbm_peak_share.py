"""Peak device memory in use over the memory JAX may use (%)."""
from harness.record import RunRecord


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    peak, limit = run.memory.get("peak_bytes_in_use"), \
        run.memory.get("bytes_limit")
    return 100.0 * peak / limit if peak and limit else None
