"""Find the knee of an open-loop cell: the highest arrival rate the
server sustains without a growing backlog.

    python3 bench/sweep.py --workload olmo1b-chat --rates 2,3,4,5,6 \
        --seconds 30 --seed 7

One process on the chip: the cell's server is built and warmed once, then
each rate runs the cell's mix (its first stream at that rate) for a
warm-up and a window. Per rate it prints the offered and completed
requests, output tokens/s, the backlog (requests in the system) at the
window's middle and end, and the TTFT and TBT tails. The rate the cell
runs at is fixed in its traffic file from this table, at 0.8 of the knee.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    """Parse the arguments, run, print; returns the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

    import jax
    import numpy as np

    from harness import traffic
    from harness.pump import Pump
    from harness.record import RunRecord, percentile
    from harness.runner import program_config, program_mesh, shape_warmup
    from harness.spec import load_cell
    from harness.weights import root_key
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import LLMServer

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: no TPU; nothing was measured", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = load_cell(args.workload, ROOT)
    m = cell.model
    cfg, sc = program_config(cell)
    mesh, layout = program_mesh(cell, jax.devices())
    server = LLMServer(cell.arch.program_params(root_key(args.seed), m),
                       cfg, sc, mesh=mesh, layout=layout)
    shape_warmup(Pump(server), cell.traffic, args.seed, m.vocab)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.traffic)
        mix["streams"][0]["arrivals"]["rate_hz"] = rate
        pump = Pump(server)
        t0 = time.monotonic()
        pending = [(t0 + r.due, r)
                   for r in traffic.generator(mix)(mix, args.seed,
                                                   args.seconds, m.vocab)]
        w0 = t0 + mix["warmup_s"]
        i = pump.run(pending, 0, until=w0 + args.seconds / 2)
        mid = sum(not r.handle.done for r in pump.records)
        pump.run(pending, i, until=w0 + args.seconds)
        w1 = time.monotonic()
        end = sum(not r.handle.done for r in pump.records)
        for r in pump.records:
            if not r.handle.done:
                r.handle.cancel()
        pump.drain()
        for r in pump.records:
            r.freeze()
        run = RunRecord(arch=cell.arch, model=m, serving=cell.serving,
                        chips=1, window=(w0, w1), setup_s=0.0,
                        records=pump.records)
        due = [r for r in pump.records if w0 <= r.due < w1]
        row = dict(rate_hz=rate, due=len(due),
                   finished=sum(r.state == "FINISHED" for r in due),
                   output_tok_s=len(run.window_tokens()) / (w1 - w0),
                   backlog_mid=mid, backlog_end=end,
                   ttft_p90_ms=1e3 * (percentile(run.ttfts_s(), 90) or 0),
                   tbt_p95_ms=1e3 * (percentile(run.gaps_s(), 95) or 0),
                   late_max_ms=1e3 * float(np.max([d for _, d in pump.late])))
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
