"""The open-loop pump that drives ``LLMServer`` and records what it did.

Requests are submitted when their due time has passed, with
``arrival_time`` set to the due time, so a stall that delays the
submission still counts in the request's time to first token. The pump
steps the server while anything is in flight and sleeps to the next due
time otherwise. It opens ``TraceAnnotation`` spans (``bench.submit``,
``bench.step``, ``bench.sleep``) around its own calls only. A
``StallWatch`` samples where the host is while one ``server.step`` runs
for over a second.
"""
from __future__ import annotations

import collections
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Counter, List, Optional, Sequence, Tuple

import jax

from harness.traffic import Req


@dataclass
class Record:
    """One submitted request as the harness saw it. ``freeze`` copies
    what the server did with it and lets go of the server."""
    handle: object            # repro.serving.RequestHandle, until frozen
    due: float                # monotonic time it was due
    submitted: float          # monotonic time it was submitted
    prompt: List[int]
    max_new: int
    stream: int
    greedy: bool = True       # sampled at temperature 0
    preload: bool = False     # admitted in set-up (see traffic.Req)
    token_times: List[float] = field(default_factory=list)
    output: List[int] = field(default_factory=list)
    state: str = ""
    finish_time: Optional[float] = None
    admitted_at: Optional[float] = None     # admission began (monotonic)

    def freeze(self) -> None:
        """Copy the request's tokens, times and state; drop the handle."""
        req = self.handle._req
        self.admitted_at = getattr(req, "admitted_at", None)
        self.token_times = list(req.token_times)
        self.output = list(req.output)
        self.state = req.state.name
        self.finish_time = req.finish_time
        self.handle = None


@dataclass
class StepSample:
    """Scheduler and pool state after one ``server.step`` (traced runs)."""
    t: float
    running: int
    slots: int
    kv_tokens: int
    creditor_tokens: int


class StallWatch:
    """While on, a thread that samples the stepping thread's stack every
    ``every_s`` once the step in progress has run ``after_s``: where the
    host is when a step stalls. ``stalls``: (start, seconds) of each such
    step; ``where``: sampled innermost frames, counted."""

    def __init__(self, after_s: float = 1.0, every_s: float = 0.05):
        self.after_s, self.every_s = after_s, every_s
        self.step_start: Optional[float] = None
        self.stalls: List[Tuple[float, float]] = []
        self.where: Counter[str] = collections.Counter()
        self._ident = threading.get_ident()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        """Start sampling (from the thread that steps the server)."""
        self._ident = threading.get_ident()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        """Stop sampling and wait for the thread to end."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join()

    def step_done(self, t0: float, t1: float) -> None:
        """Note one finished step."""
        if t1 - t0 > self.after_s:
            self.stalls.append((t0, t1 - t0))

    def _run(self) -> None:
        while not self._stop.wait(self.every_s):
            t0 = self.step_start
            if t0 is None or time.monotonic() - t0 < self.after_s:
                continue
            frame = sys._current_frames().get(self._ident)
            stack = []
            while frame is not None and len(stack) < 4:
                code = frame.f_code
                stack.append(f"{code.co_filename.rsplit('/', 2)[-1]}:"
                             f"{frame.f_lineno} {code.co_name}")
                frame = frame.f_back
            self.where[" < ".join(stack)] += 1

    def summary(self) -> str:
        """The stalled steps, how many samples were taken of those due
        (few: the sampling thread itself did not run), and where the host
        was in them."""
        due = sum(d - self.after_s for _, d in self.stalls) / self.every_s
        return (f"{len(self.stalls)} steps over {self.after_s:g} s "
                f"(seconds: {', '.join(f'{d:.3f}' for _, d in self.stalls)})"
                f"; {sum(self.where.values())} of {int(due)} samples due"
                + "".join(f"; {n}x {w}" for w, n in self.where.most_common(5)))


@dataclass
class Pump:
    """Submits due requests and steps the server."""
    server: object
    sample_steps: bool = False
    records: List[Record] = field(default_factory=list)
    samples: List[StepSample] = field(default_factory=list)
    late: List[Tuple[float, float]] = field(default_factory=list)
    stall_watch: Optional[StallWatch] = None
    _in_flight: List[Record] = field(default_factory=list)

    def submit(self, r: Req, due: float) -> Record:
        """Submit one request of the mix, due at monotonic ``due``."""
        from repro.serving import SamplingParams
        with jax.profiler.TraceAnnotation("bench.submit"):
            h = self.server.submit(
                r.prompt, SamplingParams(max_new_tokens=r.max_new,
                                         temperature=r.temperature),
                priority=r.priority, deadline_s=r.deadline_s,
                arrival_time=due)
        now = time.monotonic()
        rec = Record(h, due, now, r.prompt, r.max_new, r.stream,
                     greedy=r.temperature <= 0, preload=r.preload)
        self.records.append(rec)
        self._in_flight.append(rec)
        self.late.append((due, now - due))
        return rec

    def busy(self) -> bool:
        """Whether any submitted request is still in flight."""
        self._in_flight = [r for r in self._in_flight if not r.handle.done]
        return bool(self._in_flight)

    def step(self) -> None:
        """One ``server.step``, sampled when asked."""
        watch = self.stall_watch
        t0 = time.monotonic()
        if watch is not None:
            watch.step_start = t0
        with jax.profiler.TraceAnnotation("bench.step"):
            self.server.step()
        if watch is not None:
            watch.step_start = None
            watch.step_done(t0, time.monotonic())
        if self.sample_steps:
            self.samples.append(sample(self.server))

    def run(self, pending: Sequence[Tuple[float, Req]], i: int,
            until: float) -> int:
        """Serve open-loop until monotonic ``until``: submit
        ``pending[i:]`` (sorted (due, request) pairs) as they come due.
        Returns the index of the first request not yet submitted."""
        while True:
            now = time.monotonic()
            if now >= until:
                return i
            while i < len(pending) and pending[i][0] <= now:
                self.submit(pending[i][1], pending[i][0])
                i += 1
            if self.busy():
                self.step()
                continue
            nxt = pending[i][0] if i < len(pending) else until
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(max(0.0, min(nxt, until) - time.monotonic()))

    def drain(self, records: Optional[Sequence[Record]] = None,
              max_steps: int = 100_000) -> None:
        """Step until ``records`` (all in flight by default) are done."""
        for _ in range(max_steps):
            if records is None:
                if not self.busy():
                    return
            elif all(r.handle.done for r in records):
                return
            self.step()
        raise RuntimeError("the server made no progress")


def sample(server) -> StepSample:
    """Running slots and where running requests' KV lives, now."""
    cl = server.cluster
    running = slots = kv = cred = 0
    for i, eng in cl.engines.items():
        slots += eng.max_batch
        for r in eng.running:
            running += 1
            own = eng.rmanager.pool.tokens_of(r.req_id)
            far = sum(cl.engines[d].rmanager.pool.tokens_of(r.req_id)
                      for d in eng.remote_insts.get(r.req_id, ()))
            kv += own + far
            cred += far
    return StepSample(time.monotonic(), running, slots, kv, cred)
