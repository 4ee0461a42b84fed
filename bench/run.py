"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
BENCHMARK.json at the root of the checkout; the program under test is
``src/repro`` beside it. The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``compared``: each number
that decides ``correct`` beside its limit, also printed as the last
lines of standard error). With no TPU, too few chips or no program the
run exits 1 and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    """Parse the arguments, run, print; returns the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"run.py: no src/repro beside {BENCH}; run it from a checkout "
              f"of the repository", file=sys.stderr)
        return 1
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness.runner import NoChip, run_cell
    from harness.spec import load_cell

    cell = load_cell(args.workload, ROOT)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_START)
    except NoChip as e:
        print(f"run.py: {e}; nothing was measured", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
