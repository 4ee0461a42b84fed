"""Greedy token-stream identity of the global KV pool, mesh vs one device.

The paper's claim for the distributed KVCache is that spreading one
request's KV over several instances changes where bytes live, never what
the model computes. ``mesh_matches_one_device`` serves the same prompts
twice through ``Cluster`` with ``global_pool=True``: once with the pool's
rank axis sharded over a mesh (per-shard partials, collective LSE merge)
and once on a single device (vmapped partials, local merge). The greedy
streams must be identical. Run it in float32: the two merges reassociate
the sum differently, and bf16 rounding can flip a near-tied argmax.

Shared by the CPU test (``tests/helpers/global_check.py``, virtual
devices) and ``chip_smoke.py --chips 4`` (four TPU chips).
"""
from __future__ import annotations

import dataclasses
import gc
from typing import List, Sequence

from repro.serving import Cluster, Request, SamplingParams, ServingConfig


@dataclasses.dataclass
class ServeResult:
    """Outcome of one greedy serving run."""
    outputs: List[List[int]]      # generated tokens, per prompt
    kv_moved: int                 # bytes of KV moved between ranks
    pool_copy_steps: int          # decode steps that copied the pool
    max_ranks_spanned: int        # most ranks one request's KV used


def ranks_spanned(cluster) -> int:
    """Most ranks any running request's KV is spread over right now
    (owner plus creditors). Creditor spans are released when a request
    finishes, so callers sample this while requests run."""
    return max((1 + len(creditors) for eng in cluster.engines.values()
                for creditors in eng.remote_insts.values()), default=1)


def serve_greedy(params, cfg, config: ServingConfig,
                 prompts: Sequence[Sequence[int]], n_new: int, *,
                 mesh=None, layout=None,
                 max_steps: int = 10_000) -> ServeResult:
    """Serve ``prompts`` greedily to completion on a fresh cluster."""
    cl = Cluster(params, cfg, config, mesh=mesh, layout=layout)
    reqs = [Request(prompt=list(p),
                    sampling=SamplingParams(max_new_tokens=n_new))
            for p in prompts]
    for r in reqs:
        cl.submit(r)
    spanned = 1
    for _ in range(max_steps):
        if all(r.done for r in reqs):
            break
        cl.step()
        spanned = max(spanned, ranks_spanned(cl))
    assert all(r.done for r in reqs), [r.state for r in reqs]
    engines = cl.engines.values()
    return ServeResult(
        outputs=[list(r.output) for r in reqs],
        kv_moved=sum(e.stats.kv_moved for e in engines),
        pool_copy_steps=sum(e.stats.pool_copy_steps for e in engines),
        max_ranks_spanned=spanned)


def mesh_matches_one_device(params, cfg, config: ServingConfig,
                            prompts: Sequence[Sequence[int]], n_new: int,
                            mesh, layout):
    """Serve ``prompts`` with the global pool sharded over ``mesh``, then
    again on one device; assert identical greedy streams. Returns the
    two ``ServeResult``s (mesh first)."""
    config = config.replace(global_pool=True)
    on_mesh = serve_greedy(params, cfg, config, prompts, n_new,
                           mesh=mesh, layout=layout)
    gc.collect()    # the mesh cluster's pool leaves the devices first
    one_dev = serve_greedy(params, cfg, config, prompts, n_new)
    for i, (a, b) in enumerate(zip(on_mesh.outputs, one_dev.outputs)):
        assert a == b, (f"prompt {i}: mesh and one-device greedy streams "
                        f"differ\n mesh: {a}\n one: {b}")
    return on_mesh, one_dev
