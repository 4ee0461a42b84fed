"""Paper Fig. 11: DistAttention vs RingAttention vs head-TP (4-way).

Two measurements per method at LLaMA2-13B-class dims (nemo-12B config),
context 4K..256K on 4 ranks:
  (1) bytes moved per decode step — exact, from the algorithm;
  (2) modeled step time on v5e (compute bandwidth + interconnect),
plus a REAL wall-clock comparison of the three shard_map kernels on 4
fake CPU devices at a reduced size (collectives execute, compute real).
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

from repro.configs import get_config
from repro.distributed.hardware import V5E

try:
    from benchmarks.benchjson import write_bench_json
except ImportError:                      # run as a script from benchmarks/
    from benchjson import write_bench_json

RANKS = 4


def modeled(csv=True):
    cfg = get_config("mistral-nemo-12b")
    kvb = cfg.kv_bytes_per_token()                     # all layers
    rows = []
    for ctx in (4096, 16384, 65536, 262144):
        kv_total = ctx * kvb
        # DistAttention: q + merge partials per layer per rank.
        q = (cfg.num_heads * cfg.head_dim * 2 +
             cfg.num_heads * cfg.head_dim * 4 + 2 * cfg.num_heads * 4) \
            * cfg.num_layers * (RANKS - 1)
        # RingAttention (decode): KV blocks rotate through all ranks
        # every step: each rank ships its kv shard (RANKS-1) times.
        ring = kv_total * (RANKS - 1) / RANKS * (RANKS - 1)
        # TP by heads: KV static, but activations all-reduce per layer
        # (2 all-reduces of [1, d]) — plus kv-head replication memory.
        tp = 2 * 2 * cfg.d_model * 2 * (RANKS - 1) / RANKS \
            * cfg.num_layers
        t_mem = kv_total / (V5E.hbm_bw * RANKS)        # shared by all
        rows.append((ctx,
                     q, t_mem + q / V5E.ici_link_bw,
                     ring, t_mem + ring / V5E.ici_link_bw,
                     tp, t_mem + tp / V5E.ici_link_bw))
    if csv:
        print("fig11_ctx,dist_bytes,dist_t,ring_bytes,ring_t,"
              "tp_bytes,tp_t")
        for r in rows:
            print(f"{r[0]},{r[1]:.3e},{r[2]:.3e},{r[3]:.3e},{r[4]:.3e},"
                  f"{r[5]:.3e},{r[6]:.3e}")
    return rows


_WALL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, time
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.baselines import distattn_decode, ship_kv_decode, \
    tp_head_attention_decode
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("x",))
B, H, K, D, S = 4, 8, 8, 64, 8192
key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (B, H, D), jnp.float32)
k = jax.random.normal(key, (B, S, K, D), jnp.float32)
v = jax.random.normal(key, (B, S, K, D), jnp.float32)
mask = jnp.ones((B, S), bool)

dist = jax.jit(jax.shard_map(
    lambda q, k, v, m: distattn_decode(q, k, v, m, "x"),
    mesh=mesh, in_specs=(P(), P(None, "x"), P(None, "x"), P(None, "x")),
    out_specs=P(), check_vma=False))
ship = jax.jit(jax.shard_map(
    lambda q, k, v, m: ship_kv_decode(q, k, v, m, "x"),
    mesh=mesh, in_specs=(P(), P(None, "x"), P(None, "x"), P(None, "x")),
    out_specs=P(), check_vma=False))
tp = jax.jit(jax.shard_map(
    lambda q, k, v, m: tp_head_attention_decode(q, k, v, m),
    mesh=mesh, in_specs=(P(None, "x"), P(None, None, "x"),
                         P(None, None, "x"), P()),
    out_specs=P(None, "x"), check_vma=False))

with jax.set_mesh(mesh):
    o1 = dist(q, k, v, mask); o2 = ship(q, k, v, mask)
    o3 = tp(q, k, v, mask)
np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)
np.testing.assert_allclose(np.asarray(o1), np.asarray(o3), atol=1e-4)

def timeit(f, *a):
    f(*a)[0].block_until_ready() if isinstance(f(*a), tuple) else \
        f(*a).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        r = f(*a)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / 20 * 1e6

with jax.set_mesh(mesh):
    print(f"WALL,dist={timeit(dist,q,k,v,mask):.0f},"
          f"ship={timeit(ship,q,k,v,mask):.0f},"
          f"tp={timeit(tp,q,k,v,mask):.0f}")
"""


def wall_clock():
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # The child measures a CPU virtual-device mesh; it must never reach
    # for an accelerator the parent process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _WALL_SCRIPT, src],
                       capture_output=True, text=True, timeout=900,
                       env=env)
    if r.returncode != 0:
        raise RuntimeError(f"wall-clock child failed (rc {r.returncode}):"
                           f"\n{r.stderr[-2000:]}")
    line = next(l for l in r.stdout.splitlines() if l.startswith("WALL"))
    print("fig11_wallclock_us_cpu4dev," + line[5:])
    return line


def main():
    t0 = time.perf_counter()
    rows = modeled()
    wall_clock()
    us = (time.perf_counter() - t0) * 1e6
    r = rows[-1]
    print(f"bench_distattn_methods,{us:.1f},"
          f"ring_over_dist_bytes_262k={r[3] / r[1]:.0f}x")
    write_bench_json(
        "distattn_methods", rows=rows,
        config={"model": "mistral-nemo-12b", "ranks": RANKS},
        header=["ctx", "dist_bytes", "dist_t", "ring_bytes", "ring_t",
                "tp_bytes", "tp_t"],
        metrics={"ring_over_dist_bytes_262k": r[3] / r[1]})


if __name__ == "__main__":
    main()
