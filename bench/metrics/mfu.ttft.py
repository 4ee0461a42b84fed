"""The whole step's share of the bf16 peak, as ``mfu``, read beside the
prefill kernel's roofline in the cells whose requests arrive (%)."""
from harness.record import RunRecord, load_reader

_mfu = load_reader("mfu")


def read(run: RunRecord):
    """This metric of ``run``; None when the run has nothing to read."""
    return _mfu(run)
