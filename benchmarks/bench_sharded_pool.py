"""Mesh-sharded global KV pool: decode-throughput scaling over ranks.

The tentpole claim of the global pool is that adding ranks adds
serving capacity WITHOUT moving KV: each rank's shard computes its
paged MicroAttention partial in place and only the per-token LSE-merge
scalars (o, m, l) cross the mesh. This bench measures wall-clock decode
tokens/s of the in-process cluster running over ONE mesh-sharded
[R, L, NB, bs, K, hd] tensor at R = 1, 2, 4 ranks, with the offered
load scaled with R (every rank serves a full decode batch), and reports
the analytic per-step collective bytes of the merge alongside.

Gated metric: ``tps_ratio_4_over_1`` — aggregate throughput at 4 ranks
over 1 rank. On CPU the "mesh" is fake host devices sharing the same
cores, so the ratio is far below 4x; the gate only catches the pooled
step's cross-rank plumbing getting slower (e.g. a merge that starts
shipping KV instead of scalars). ``tps_r*`` rows are informational.

Mesh-rank scaling needs ``--xla_force_host_platform_device_count`` set
BEFORE jax imports, so main() re-execs this file as a subprocess worker
with the flag in its environment (same pattern as the sharded tests).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

RANKS = (1, 2, 4)
N_NEW = 24
PER_RANK_REQS = 2


def worker():
    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models.model import init_params
    from repro.serving import (Cluster, Request, SamplingParams,
                               ServingConfig)
    from repro.serving.sharded_step import ServeLayout

    cfg = get_smoke_config("olmo-1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    L, H, hd = cfg.num_layers, cfg.num_heads, cfg.head_dim
    rng = np.random.default_rng(0)
    out = []
    for R in RANKS:
        mesh = make_mesh((R, 1), ("data", "model"))
        layout = ServeLayout(batch_axes=("data",), pool_axes=("data",))
        prompts = [list(rng.integers(0, cfg.vocab_size, size=12))
                   for _ in range(PER_RANK_REQS * R)]

        def run():
            cl = Cluster(params, cfg, ServingConfig.smoke(
                n_instances=R, max_batch=PER_RANK_REQS, pool_blocks=48,
                global_pool=True, schedule_every=1000),
                mesh=mesh, layout=layout)
            reqs = [Request(prompt=p,
                            sampling=SamplingParams(max_new_tokens=N_NEW))
                    for p in prompts]
            for r in reqs:
                cl.submit(r)
            t0 = time.perf_counter()
            cl.run_until_done(max_steps=600)
            dt = time.perf_counter() - t0
            assert all(r.done for r in reqs)
            return sum(len(r.output) for r in reqs) / dt

        run()                            # warm the jit signatures
        tps = run()
        # Per decode step each of R shards contributes its (o, m, l)
        # partial to the collective merge for every slot on every layer:
        # o = H*hd floats, m + l = 2*H floats, f32 scalars on the wire.
        batch = PER_RANK_REQS * R
        coll_bytes = (R - 1) * L * batch * (H * hd + 2 * H) * 4
        out.append({"ranks": R, "tps": tps,
                    "collective_bytes_per_step": coll_bytes})
    print("WORKER_RESULT " + json.dumps(out))


def main():
    try:
        from benchmarks.benchjson import write_bench_json
    except ImportError:
        from benchjson import write_bench_json

    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4")
    # The worker measures a CPU virtual-device mesh; it must never reach
    # for an accelerator the parent process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src"))
    t0 = time.perf_counter()
    r = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--worker"], env=env, capture_output=True,
                       text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"sharded-pool worker failed:\n{r.stdout}\n"
                           f"{r.stderr}")
    line = next(l for l in r.stdout.splitlines()
                if l.startswith("WORKER_RESULT "))
    rows = json.loads(line[len("WORKER_RESULT "):])
    us = (time.perf_counter() - t0) * 1e6
    by_rank = {row["ranks"]: row for row in rows}
    ratio = by_rank[4]["tps"] / by_rank[1]["tps"]
    print("sharded_pool_ranks,tokens_per_s,collective_bytes_per_step")
    for row in rows:
        print(f"{row['ranks']},{row['tps']:.2f},"
              f"{row['collective_bytes_per_step']}")
    print(f"bench_sharded_pool,{us:.1f},tps_ratio_4_over_1={ratio:.3f}")
    write_bench_json(
        "sharded_pool",
        rows=[[row["ranks"], row["tps"],
               row["collective_bytes_per_step"]] for row in rows],
        config={"model": "olmo-1b-smoke", "ranks": list(RANKS),
                "per_rank_reqs": PER_RANK_REQS, "n_new": N_NEW,
                "pool_axes": ["data"], "backend": "cpu-fake-devices"},
        header=["ranks", "tokens_per_s", "collective_bytes_per_step"],
        metrics={"tps_ratio_4_over_1": ratio,
                 "tps_r1": by_rank[1]["tps"],
                 "tps_r4": by_rank[4]["tps"]})


if __name__ == "__main__":
    if "--worker" in sys.argv:
        worker()
    else:
        main()
