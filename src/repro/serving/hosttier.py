"""Host-DRAM KV tier: the memory level below the device block pools.

The paper pools KV across DEVICE memories; this module adds the missing
level of the hierarchy: a bounded store of host-memory block frames that
cold blocks (finished requests' cached prefixes, reclaimed hosted
spans, preempted requests) spill into instead of being dropped, and
from which a prefix-cache hit prefetches them back.

Both directions are ASYNCHRONOUS, mirroring PR 4's movement overlap:

* **Spill (D2H)** — ``put`` takes the device rows (the gather result of
  ``read_pool_rows``; an independent buffer, so the pool block can be
  freed and reused immediately — JAX's functional semantics order the
  gather before any later in-place pool update) and dispatches
  ``copy_to_host_async``. The transfer completes behind decode compute;
  ``drain()`` (called once per cluster step) finalizes whichever
  transfers have landed without blocking.
* **Prefetch (H2D)** — ``get`` returns the host rows; the caller's
  ``write_pool_rows`` dispatch is itself async, so the H2D upload also
  hides behind compute and is only waited on at the admission's
  table-commit point. ``get`` on a spill still in flight must block —
  that is a PREFETCH STALL, counted in ``fetch_stalls`` (the
  ``bench_prefix_cache`` overlap gate divides these by decode steps).

Eviction is LRU with a watermark pair: when occupancy crosses
``high_watermark`` the tier evicts least-recently-used frames down to
``low_watermark``. Pinned keys (an in-flight prefetch chain, an
``evictable_fn`` veto from the prefix cache) are skipped; ``on_evict``
lets the owner drop dependent state — the radix cache deletes the
evicted node's now-unreachable subtree there.
"""
from __future__ import annotations

import time
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.serving.faults import (FrameCorruptionError, TransferError,
                                  backoff_delay_s)
from repro.serving.kvpool import rows_nbytes
from repro.serving.tracing import Tracer


@dataclass
class HostTierStats:
    """Counters for tier traffic, stalls, and eviction pressure."""

    spilled_bytes: int = 0       # D2H bytes accepted by put()
    fetched_bytes: int = 0       # H2D bytes handed out by get()
    spills: int = 0
    fetches: int = 0
    fetch_stalls: int = 0        # get() had to block on an in-flight D2H
    evictions: int = 0
    rejected: int = 0            # put() refused (tier full of pinned keys)
    fetch_retries: int = 0       # transient fetch errors absorbed by retry
    fetch_failures: int = 0      # fetches that exhausted the retry budget
    corruptions: int = 0         # frames that failed hash verification


class HostKVTier:
    """Bounded LRU store of host-memory KV block frames.

    Keys are content hashes (the radix cache's node hashes) or any
    hashable id; one key maps to ONE block's (k, v) rows of shape
    ``[L, block_size, K, hd]``.

    With ``verify=True`` every frame is checksummed (CRC32 of its raw
    bytes) when the D2H spill finalizes, and every ``get`` re-checks the
    stored bytes against that hash before handing them out — a
    corrupted or swapped frame raises ``FrameCorruptionError`` (and is
    dropped) instead of silently poisoning decode. Transient fetch
    errors (``TransferError``, e.g. an injected chaos fault) are
    retried up to ``max_retries`` times with bounded exponential
    backoff before propagating.
    """

    def __init__(self, capacity_blocks: int, *,
                 high_watermark: float = 0.9, low_watermark: float = 0.7,
                 on_evict: Optional[Callable[[Any], None]] = None,
                 evictable_fn: Optional[Callable[[Any], bool]] = None,
                 verify: bool = False, max_retries: int = 0,
                 backoff_base_s: float = 0.0, backoff_max_s: float = 0.05,
                 tracer: Optional[Tracer] = None):
        assert capacity_blocks >= 0
        assert 0.0 < low_watermark <= high_watermark <= 1.0
        self.capacity = capacity_blocks
        self.high = high_watermark
        self.low = low_watermark
        self.on_evict = on_evict
        self.evictable_fn = evictable_fn
        self.verify = verify
        self.max_retries = max(0, max_retries)
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        # key -> (k_np, v_np) finalized frames.
        self._frames: Dict[Any, Tuple[np.ndarray, np.ndarray]] = {}
        # key -> (k_dev, v_dev) with copy_to_host_async dispatched.
        self._pending: Dict[Any, Tuple[Any, Any]] = {}
        self._sums: Dict[Any, int] = {}       # key -> stored-frame CRC32
        self._tick: Dict[Any, int] = {}       # key -> LRU clock value
        self._clock = 0
        self.pinned: set = set()
        self.stats = HostTierStats()
        # Chaos hook: called with the key on each fetch; may return
        # "error" (inject a transient TransferError) or "corrupt"
        # (bit-flip the stored frame). See serving.faults.
        self.fault_hook: Optional[Callable[[Any], Optional[str]]] = None
        self.tracer = tracer if tracer is not None else Tracer()

    # ----------------------------------------------------------------- #
    @property
    def used_blocks(self) -> int:
        """Frames resident or in flight (both count against capacity)."""
        return len(self._frames) + len(self._pending)

    @property
    def free_blocks(self) -> int:
        """Capacity headroom without evicting anything."""
        return max(0, self.capacity - self.used_blocks)

    def __contains__(self, key: Any) -> bool:
        return key in self._frames or key in self._pending

    def pin(self, key: Any) -> None:
        """Exempt ``key`` from LRU eviction until ``unpin``/``drop``.

        The preemptor pins every frame of a paused request's KV chain:
        a paused request must ALWAYS be resumable byte-identically, so
        its frames can never be sacrificed to watermark pressure."""
        self.pinned.add(key)

    def unpin(self, key: Any) -> None:
        """Make ``key`` LRU-evictable again (no-op if not pinned)."""
        self.pinned.discard(key)

    def _touch(self, key: Any) -> None:
        self._clock += 1
        self._tick[key] = self._clock

    # ----------------------------------------------------------------- #
    def put(self, key: Any, k_dev: Any, v_dev: Any) -> bool:
        """Spill one block's device rows to host, asynchronously.

        ``k_dev``/``v_dev``: [L, block_size, K, hd] device arrays (the
        latent family: k [L, block_size, W] and v None) that
        do NOT alias the pool (a gather result). Returns False when the
        tier cannot make room (capacity 0 or everything pinned) — the
        caller then simply drops the block, the pre-tier behavior.
        """
        if self.capacity <= 0:
            self.stats.rejected += 1
            return False
        if key in self:
            self._touch(key)
            return True
        if self.used_blocks + 1 > self.capacity and \
                not self._evict_down(self.capacity - 1):
            self.stats.rejected += 1
            return False
        for a in (x for x in (k_dev, v_dev) if x is not None):
            try:
                a.copy_to_host_async()
            except Exception:
                pass                     # backend without async D2H
        self._pending[key] = (k_dev, v_dev)
        self._touch(key)
        self.stats.spills += 1
        self.stats.spilled_bytes += rows_nbytes(k_dev, v_dev)
        if self.used_blocks > int(self.high * self.capacity):
            self._evict_down(int(self.low * self.capacity))
        return True

    def drain(self, block: bool = False) -> None:
        """Finalize spill transfers that have landed (all of them when
        ``block`` is True). Called once per cluster step so host frames
        materialize behind decode compute, never on its critical path."""
        done: List[Any] = []
        for key, (k, v) in self._pending.items():
            if not block and not (self._is_ready(k) and self._is_ready(v)):
                continue
            self._finalize(key, k, v)
            done.append(key)
        for key in done:
            del self._pending[key]

    def _finalize(self, key: Any, k: Any, v: Any) -> None:
        # The landed host bytes are the frame of record: the content
        # hash every later fetch is verified against is taken HERE.
        frame = (np.asarray(k), None if v is None else np.asarray(v))
        self._frames[key] = frame
        if self.verify:
            self._sums[key] = self._checksum(frame)

    @staticmethod
    def _checksum(frame: Tuple[np.ndarray, np.ndarray]) -> int:
        crc = zlib.crc32(frame[0].tobytes())
        return crc if frame[1] is None else zlib.crc32(frame[1].tobytes(),
                                                       crc)

    @staticmethod
    def _is_ready(a: Any) -> bool:
        try:
            return bool(a.is_ready())
        except Exception:
            return True

    # ----------------------------------------------------------------- #
    def get(self, key: Any) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Host rows for ``key`` — finalizing (and counting as a stall)
        a spill that is still in flight.

        Raises ``TransferError`` after ``max_retries`` failed fetch
        attempts and ``FrameCorruptionError`` (dropping the frame) when
        verification does not match the stored content hash; returns
        None only for a genuinely absent key (raced eviction)."""
        attempt = 0
        while True:
            try:
                return self._get_once(key)
            except TransferError:
                if attempt >= self.max_retries:
                    self.stats.fetch_failures += 1
                    raise
                self.stats.fetch_retries += 1
                delay = backoff_delay_s(attempt, self.backoff_base_s,
                                        self.backoff_max_s)
                if delay > 0:
                    time.sleep(delay)
                attempt += 1

    def _get_once(self, key: Any) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        if key in self._pending:
            k, v = self._pending.pop(key)
            if self._is_ready(k) and self._is_ready(v):
                self._finalize(key, k, v)
            else:
                with self.tracer.span("serve.sync", tag="host_fetch"):
                    self._finalize(key, k, v)
                self.stats.fetch_stalls += 1
        if key in self._frames and self.fault_hook is not None:
            mode = self.fault_hook(key)
            if mode == "error":
                raise TransferError(f"injected host fetch error "
                                    f"(key={key!r})")
            if mode == "corrupt":
                self._corrupt(key)
        frame = self._frames.get(key)
        if frame is None:
            return None
        if self.verify and key in self._sums and \
                self._checksum(frame) != self._sums[key]:
            self.stats.corruptions += 1
            self.drop(key)
            raise FrameCorruptionError(
                f"host frame {key!r} failed content-hash verification")
        self._touch(key)
        self.stats.fetches += 1
        self.stats.fetched_bytes += rows_nbytes(*frame)
        return frame

    def _corrupt(self, key: Any) -> None:
        # Chaos injection: flip the first byte of the stored K rows —
        # exactly what a wrong/bit-rotted frame looks like to a reader.
        k, v = self._frames[key]
        kb = bytearray(k.tobytes())
        kb[0] ^= 0xFF
        self._frames[key] = (
            np.frombuffer(bytes(kb), dtype=k.dtype).reshape(k.shape), v)

    def drop(self, key: Any) -> None:
        """Forget ``key`` entirely (pending or resident; idempotent)."""
        self._pending.pop(key, None)
        self._frames.pop(key, None)
        self._sums.pop(key, None)
        self._tick.pop(key, None)
        self.pinned.discard(key)

    # ----------------------------------------------------------------- #
    def _evict_down(self, target_blocks: int) -> bool:
        """LRU-evict unpinned frames until occupancy <= target. Returns
        True if the target was reached."""
        order = sorted((k for k in self._tick if k in self),
                       key=lambda k: self._tick[k])
        for key in order:
            if self.used_blocks <= target_blocks:
                break
            if key in self.pinned:
                continue
            if self.evictable_fn is not None and \
                    not self.evictable_fn(key):
                continue
            self.stats.evictions += 1
            if self.on_evict is not None:
                # The owner's hook drops dependent state and is expected
                # to call ``drop(key)`` (the radix cache deletes the
                # node's subtree, which includes this frame).
                self.on_evict(key)
            self.drop(key)               # idempotent if the hook dropped
        return self.used_blocks <= target_blocks


__all__ = ["HostKVTier", "HostTierStats"]
