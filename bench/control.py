"""Readings that set a cell's correctness limit: the program's widest
logit gap and the float8 control's, on many seeds, in one process.

    python3 bench/control.py --workload olmo1b-chat --seconds 20 \
        --seeds 101,102,103

Each seed is a whole run of the cell (weights, warm-up, a window at the
cell's own load, the reference over the sample it served); the control
is the same reference computed in float8 e4m3 (every matmul's operands),
judged by the token it puts first at each position the sample served,
by the same numbers and limits as the program. The limit lies above the
largest program reading and below the smallest control reading. Exits 1
when a control run comes out correct: the limit then does not separate
the two. The benchmark's own runs do not run the control.
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
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness.runner import NoChip, run_cell
    from harness.spec import load_cell

    cell = load_cell(args.workload, ROOT)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = run_cell(cell, seed, args.seconds, False,
                           time.monotonic(), control=True)
        except NoChip as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 1
        ctl = res["control"]
        row = {"seed": seed, "program_gap":
               res["compared"]["max_logit_gap"]["value"],
               "control_gap": ctl["compared"]["max_logit_gap"]["value"],
               "correct": res["correct"], "control_correct": ctl["correct"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"readings": rows}))
    if any(r["control_correct"] for r in rows):
        print("control.py: the control came out correct on seeds "
              f"{[r['seed'] for r in rows if r['control_correct']]}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
