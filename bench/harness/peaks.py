"""Published peaks of each chip, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import os
from typing import Any, Dict

from harness.spec import BENCH_DIR, load_json


def peaks(device_kind: str) -> Dict[str, Any]:
    """The peaks of ``device_kind``; a chip not in the table is an error."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
