"""Async double-buffered staging of KV pool-row movement.

JAX dispatch is asynchronous: a functional pool update
(``read_pool_rows`` -> ``write_pool_rows`` / ``scatter_pool_rows``)
returns new Array handles immediately while the copies execute behind
the host. Data correctness therefore never depends on WHEN the host
waits — the functional dependencies order every read against every
(donated, in-place) write. What the sync policy does decide is whether
movement traffic hides behind decode compute (paper Fig. 12) or is paid
serially on top of it, and that is exactly what ``AsyncStager`` makes
explicit and measurable:

* ``overlap=False`` — the serial baseline: every staged copy chain is
  ``block_until_ready``-ed at dispatch, the behavior of a synchronous
  DMA engine. Movement time adds to step time.
* ``overlap=True`` — up to ``depth`` copy chains stay in flight
  (double-buffered by default, matching the classic two-slot staging
  buffer); the host blocks only when the ring is full or at an explicit
  ``commit()`` — the table-commit points where a span must be fully
  resident before its tables go live to a consumer that cannot be
  ordered through array dependencies (e.g. handing a pool to another
  process or a benchmark reading raw buffers).

``bench_kv_movement`` A/Bs the two policies (``tps_overlap_on/off``) and
reports the measured break-even next to the paper's modeled
16-tokens/step figure; ``tests/test_zero_copy.py`` asserts the A/B is
token-identical.
"""
from __future__ import annotations

import time
from collections import defaultdict, deque
from typing import Any, Callable, Deque, Dict, Optional, Tuple

import jax

from repro.serving.faults import TransferError, backoff_delay_s
from repro.serving.tracing import Tracer


class AsyncStager:
    """Bounded in-flight window over dispatched pool-row copy chains.

    Chains may carry a ``tag`` ("prefetch", "spill", ...): per-tag stall
    counters record how often draining a tagged chain actually had to
    WAIT — the copy was still in flight when the host needed it done.
    ``bench_prefix_cache`` gates prefetch stalls per decode step with
    these.

    Failure handling: draining a chain that raises ``TransferError``
    (e.g. an injected ``FaultPlan`` timeout) is retried up to
    ``max_retries`` times with bounded exponential backoff (counted in
    ``retries`` per tag). On exhaustion — or any non-transient error —
    the failure is counted in ``failures`` per tag, the REMAINING
    in-flight ring is drained to a clean state (secondary errors are
    counted, not raised), and the original error propagates instead of
    being swallowed with a half-populated ring.
    """

    def __init__(self, overlap: bool = True, depth: int = 2, *,
                 max_retries: int = 0, backoff_base_s: float = 0.0,
                 backoff_max_s: float = 0.05,
                 tracer: Optional[Tracer] = None):
        self.overlap = overlap
        self.depth = max(1, depth)
        self.max_retries = max(0, max_retries)
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        self._inflight: Deque[Tuple[Any, Optional[str]]] = deque()
        self.staged = 0          # copy chains handed to the stager
        self.synced = 0          # explicit block_until_ready calls
        self.sync_wait_s = 0.0   # serve.sync seconds: blocked on copies
        self.stalls: Dict[str, int] = defaultdict(int)
        self.retries: Dict[str, int] = defaultdict(int)
        self.failures: Dict[str, int] = defaultdict(int)
        # Chaos hook: called with the chain's tag before each wait; a
        # True return injects one TransferError (see serving.faults).
        self.fault_hook: Optional[Callable[[Optional[str]], bool]] = None
        self.tracer = tracer if tracer is not None else Tracer()

    def stage(self, arrays: Any, tag: Optional[str] = None) -> None:
        """Register one dispatched copy chain (any pytree of arrays).

        Serial mode blocks immediately; overlap mode admits it into the
        in-flight ring and only drains the OLDEST chain when the ring
        exceeds ``depth`` — the double-buffer rotation.
        """
        self.staged += 1
        if not self.overlap:
            self._block(arrays, tag)
            return
        self._inflight.append((arrays, tag))
        while len(self._inflight) > self.depth:
            self._block(*self._inflight.popleft())

    def commit(self) -> None:
        """Barrier at a table-commit point: drain every in-flight chain."""
        while self._inflight:
            self._block(*self._inflight.popleft())

    def _block(self, arrays: Any, tag: Optional[str] = None) -> None:
        # Retry wrapper around the actual wait. The chain was already
        # popped from the ring by the caller, so a chain that ultimately
        # fails is never left in flight.
        name = tag or "untagged"
        attempt = 0
        while True:
            try:
                self._wait_ready(arrays, tag)
                return
            except TransferError:
                if attempt < self.max_retries:
                    self.retries[name] += 1
                    delay = backoff_delay_s(attempt, self.backoff_base_s,
                                            self.backoff_max_s)
                    if delay > 0:
                        time.sleep(delay)
                    attempt += 1
                    continue
                self.failures[name] += 1
                self._drain_after_failure()
                raise
            except Exception:
                self.failures[name] += 1
                self._drain_after_failure()
                raise

    def _drain_after_failure(self) -> None:
        # Leave the ring EMPTY and consistent after a failed chain:
        # secondary errors while flushing the survivors are counted but
        # not raised (the primary error is the one that propagates).
        pending, self._inflight = list(self._inflight), deque()
        for arrays, tag in pending:
            try:
                self._wait_ready(arrays, tag)
            except Exception:
                self.failures[tag or "untagged"] += 1

    def _wait_ready(self, arrays: Any, tag: Optional[str] = None) -> None:
        if self.fault_hook is not None and self.fault_hook(tag):
            raise TransferError(
                f"injected stager transfer timeout (tag={tag!r})")
        # A staged handle may since have been DONATED into a successor
        # update (the zero-copy chain); its buffer lives on inside the
        # successor, which is itself staged — so deleted handles are
        # simply skipped rather than waited on.
        live = [x for x in jax.tree.leaves(arrays)
                if not (hasattr(x, "is_deleted") and x.is_deleted())]
        stalled = any(not x.is_ready() for x in live
                      if hasattr(x, "is_ready"))
        with self.tracer.span("serve.sync", tag=tag or "untagged") as sync:
            jax.block_until_ready(live)
        self.sync_wait_s += sync.seconds
        self.synced += 1
        if stalled and tag is not None:
            self.stalls[tag] += 1
