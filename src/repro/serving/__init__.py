"""Infinite-LLM serving runtime: request-lifecycle frontend over a
DistAttention cluster.

Public API (start here)
-----------------------
``LLMServer`` is the serving frontend — the API everything outside this
package uses:

    from repro.serving import LLMServer, ServingConfig, SamplingParams

    server = LLMServer(params, cfg, ServingConfig.smoke(n_instances=3))
    handle = server.submit(prompt_tokens,
                           SamplingParams(max_new_tokens=32),
                           priority=1, deadline_s=2.0)
    for tok in handle.tokens():      # incremental stream
        ...
    handle.result(); handle.status; handle.metrics; handle.cancel()

    stats = server.run(arrivals)     # open-loop trace pump:
    stats["ttft_p99"], stats["tbt_p99"]

``ServingConfig`` is the one typed, frozen home of every serving knob
(cluster shape, KV pool, movement, Algorithm-1 thresholds, admission
backpressure), with ``smoke()``/``v5e()`` presets. Cancellation
propagates through every layer: engine slot, in-flight streaming
prefill (creditor reservations rolled back via the all-or-nothing
machinery), hosted spans, and planned KV moves (-> ``MoveResult.GONE``).

Prefix caching + the host-DRAM KV tier (opt-in)
-----------------------------------------------
Two ``ServingConfig`` knobs extend the paper's device-pooled memory one
level down and across requests:

    ServingConfig.smoke(prefix_cache=True,     # radix prefix cache
                        host_tier_blocks=4096) # host-DRAM spill tier

With ``prefix_cache=True`` every finished request's full KV blocks are
adopted (zero-copy, refcounted) into a ``RadixPrefixCache`` — a radix
tree over content-hashed block chains. A later request walks its
longest cached prefix at admission, pins the matching frames, and
streams prefill only for the uncached tail; a full-prompt hit shares
all but the last block and copies that one (copy-on-write), so cached
and cold admissions emit byte-identical KV and therefore identical
tokens. With ``host_tier_blocks > 0`` cold replicas spill to a
``HostKVTier`` of host-memory frames (async D2H behind compute, LRU
watermarks) instead of being dropped, and a later hit prefetches them
back (H2D through the stager) — ``server.metrics`` surfaces occupancy,
hit tokens, and spill/prefetch bytes; ``bench_prefix_cache`` gates warm
TTFT >= 2x cold and prefetch stalls <= 0.1 in CI.

Overload survival (opt-in)
--------------------------
``ServingConfig(overload=OverloadPolicy(enabled=True))`` lets the
frontend PAUSE running requests instead of making deadline-urgent
arrivals wait out the queue:

    ServingConfig.smoke(overload=OverloadPolicy(enabled=True))

When the admission queue backs up with work more urgent than what is
running, the ``Preemptor`` ranks victims by SLO slack (deadline minus
the perf model's predicted finish, charged the spill+resume round trip
— no-deadline best-effort requests rank first), pauses the chosen
victim at a step boundary, and spills its KV chain byte-for-byte to a
dedicated pinned host tier; creditor spans are released exactly once
and a mid-prefill pause reuses the cancel path's exact rollback but
re-queues the request. Resume restores the frames through the paged
admission path — no re-prefill — so a resumed request's greedy tokens
are identical to an unpreempted run (CI-gated as
``preempt_token_identity``; ``bench_overload`` also gates >= 1.3x
deadline goodput over the queue-only baseline at 2x overload). The
``ArrivalEstimator`` EWMA replaces the static ``avg_new_req_len`` knob
in Algorithm-1 planning while the server runs. ``server.metrics``
surfaces ``preemptions`` / ``preempt_resumes`` / ``paused_now`` /
``arrival_rate_hz``; knobs live on ``OverloadPolicy`` (see
``docs/ARCHITECTURE.md`` for the full reference).

Mesh-sharded global KV pool (opt-in)
------------------------------------
``ServingConfig(global_pool=True)`` folds the per-instance pool tensors
into ONE cluster-wide ``GlobalKVPool`` array ``[ranks, L, NB, bs, K,
hd]`` whose rank axis can be sharded over a device mesh:

    mesh = make_mesh((2, 4), ("data", "model"))   # repro.launch.mesh
    layout = ServeLayout(batch_axes=("data",), pool_axes=("data",))
    server = LLMServer(params, cfg,
                       ServingConfig.v5e(global_pool=True),
                       mesh=mesh, layout=layout)

Knobs: ``ServingConfig.global_pool`` turns the mode on;
``LLMServer(..., mesh=...)`` attaches the mesh (omit it for the
single-device vmap path — same math, no collectives); ``layout``
(``ServeLayout.pool_axes``) picks which mesh axes shard the rank axis
(``("data",)`` or ``("data", "model")``; n_instances must divide their
total size). Every engine's rManager then aliases its ``RankKVPool``
slice of the global allocator, decode/prefill run
``decode_step_global``/``prefill_chunk_global`` (per-rank paged
partials under ``shard_map``, LSE-merged with pmax/psum — queries are
broadcast, KV never moves), and ``StripedMove`` legs, streaming
creditor writes, and prefix-cache materialization become slice
assignments inside the one tensor (remote DMA under GSPMD). The
donated-buffer zero-copy discipline is unchanged and CI-gated
(``decode_pool_zero_copy``); ``bench_sharded_pool`` gates rank-scaling
throughput.

Fault tolerance (always on; chaos injection opt-in)
---------------------------------------------------
Pooled KV means one failed creditor rank can hold pieces of OTHER
instances' requests, so the runtime detects, quarantines, and recovers
deterministically. Detection: an instance that misses
``FaultPolicy.heartbeat_timeout_steps`` consecutive heartbeats (or the
wall-clock ``heartbeat_timeout``) is marked DEAD — its view leaves
Algorithm-1 planning, it can never be picked as a creditor or owner
again, and its allocator (a quarantined slice of the one tensor in
global-pool mode) is drained wholesale. Recovery is TOKEN REPLAY:
every request that lost KV on the dead rank re-admits through the
normal paged admission path, re-prefilling ``prompt + output[:-1]``
(its emitted tokens are known — no resampling), so the greedy
continuation is byte-identical to an unfailed oracle (CI-gated as
``recovery_token_identity`` in both pool modes). Transfers retry with
bounded exponential backoff; host-tier fetches are verified against
the content hash the frame was stored under (a corrupted frame raises
instead of poisoning decode, then falls back to replay); a move stripe
whose leg fails mid-execution rolls back exactly and re-plans against
surviving creditors. Chaos testing: build a seedable ``FaultPlan``
(crash / heartbeat silence / move-leg failure / host fetch error /
frame corruption / stager timeout, each fireable at a chosen step) and
arm it with ``cluster.install_faults(plan)``; ``server.metrics``
surfaces ``dead_instances`` / ``fault_recoveries`` /
``replayed_tokens`` / ``transfer_retries`` and friends. Knobs live on
``FaultPolicy`` (see ``docs/ARCHITECTURE.md``); ``bench_chaos`` gates
recovery identity and goodput-under-crash in CI.

Observability (always on)
-------------------------
The serving loop opens named trace spans (``serve.step``,
``serve.engine``, ``serve.admit``, ``serve.readback``, ...; the list is
``repro.serving.tracing.NAMES``) at each layer boundary. A profile
taken around serving (``jax.profiler.trace``) holds them on the device
trace's clock, so every idle gap of the device is named by the host
work under it; with no profile running they only add to running
totals, which ``server.metrics`` reports as ``trace.<name>.s`` and
``trace.<name>.n``. ``Request.admitted_at`` stamps when admission
first started, so queue wait is ``admitted_at - arrival_time``.

Internal layers (exported for tests/benchmarks, not the serving API)
--------------------------------------------------------------------
``Cluster`` executes steps: N ``InstanceEngine``s (each owning a
device-resident paged KV pool addressed through ``RankKVPool`` block
tables) plus a ``GManager`` running the paper's Algorithm 1 via
``GreedyScheduler``. Driving ``cluster.step()`` by hand is the OLD
batch-mode pattern — new code should go through ``LLMServer``.
"""
from repro.serving.cluster import Cluster
from repro.serving.config import (FaultPolicy, OverloadPolicy,
                                  ServingConfig)
from repro.serving.engine import InstanceEngine
from repro.serving.faults import (FaultEvent, FaultInjector, FaultPlan,
                                  FaultStats, FrameCorruptionError,
                                  TransferError)
from repro.serving.gmanager import GManager
from repro.serving.globalpool import GlobalKVPool
from repro.serving.hosttier import HostKVTier
from repro.serving.kvpool import BlockAllocator, RankKVPool
from repro.serving.preempt import Preemptor, PreemptStats
from repro.serving.prefixcache import RadixPrefixCache
from repro.serving.perfmodel import InstancePerfModel, cluster_tps
from repro.serving.request import (Request, RequestIdAllocator,
                                   RequestState, SamplingParams)
from repro.serving.rmanager import RManager
from repro.serving.scheduler import (GreedyScheduler, InstanceView,
                                     SpanLeg, StripedMove)
from repro.serving.server import Arrival, LLMServer, RequestHandle
from repro.serving.tracing import Tracer

__all__ = [
    "LLMServer", "RequestHandle", "Arrival", "ServingConfig",
    "OverloadPolicy", "Preemptor", "PreemptStats",
    "Cluster", "InstanceEngine", "GManager", "BlockAllocator", "RankKVPool",
    "InstancePerfModel", "cluster_tps", "Request", "RequestIdAllocator",
    "RequestState", "SamplingParams", "RManager", "GreedyScheduler",
    "InstanceView", "SpanLeg", "StripedMove", "HostKVTier",
    "RadixPrefixCache", "GlobalKVPool",
    "FaultPolicy", "FaultPlan", "FaultEvent", "FaultInjector",
    "FaultStats", "TransferError", "FrameCorruptionError", "Tracer",
]
