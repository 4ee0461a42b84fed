"""Trace spans of the serving loop: named host intervals on the
profiler's clock, with running totals per name.

A *trace span* (not to be confused with a creditor's KV span,
``Request.spans``) marks one stretch of host work at a layer boundary
of the serving loop: the frontend step, dispatch, heartbeats, the
Algorithm-1 plan round, each KV move, each engine step, each admission,
the decode table build, the decode dispatch, sampling, the sampled-token
readback, stager/host-tier waits and the end-of-step drains.

Each span does two things:

* it opens ``jax.profiler.TraceAnnotation(name, **ids)``, which costs
  next to nothing unless a profiler session is running, and is then
  written into the profile on the device trace's clock — so every idle
  gap of the device lies under a named piece of host work;
* it adds its ``perf_counter`` seconds and one count to running totals
  per name, always. ``LLMServer.metrics`` reports them as
  ``trace.<name>.s`` and ``trace.<name>.n``.

There is no switch: taking a profile is what turns the annotations on.
The ``Cluster`` owns one ``Tracer``; its engines, stager, host tiers,
preemptor and the ``LLMServer`` share it. Spans are never opened inside
a per-slot or per-token loop.
"""
from __future__ import annotations

import time
from typing import Dict

import jax

# Every trace span the serving loop opens; the totals hold each name
# from the start, so an operator's dashboard never misses a key.
NAMES = (
    "serve.step",        # LLMServer.step, whole
    "serve.dispatch",    # frontend dispatch + overload control
    "serve.heartbeat",   # heartbeats, liveness, failure handling
    "serve.plan",        # the Algorithm-1 plan round
    "serve.move",        # one KV move (reactive or planned)
    "serve.engine",      # one engine's step
    "serve.admit",       # one admission, through its first token
    "serve.build",       # decode table and input build
    "serve.decode",      # decode step dispatch
    "serve.sample",      # sampling dispatch
    "serve.readback",    # sampled tokens copied to the host
    "serve.sync",        # host blocked on in-flight KV copies
    "serve.drain",       # host-tier drains + release of finished KV
)


class Span:
    """One open trace span; ``seconds`` holds its length once closed."""

    __slots__ = ("_tracer", "_name", "_note", "_t0", "seconds")

    def __init__(self, tracer: "Tracer", name: str, ids: Dict[str, object]):
        self._tracer = tracer
        self._name = name
        self._note = jax.profiler.TraceAnnotation(name, **ids)
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._note.__exit__(*exc)
        self._tracer.seconds[self._name] += self.seconds
        self._tracer.counts[self._name] += 1


class Tracer:
    """Opens the serving loop's trace spans and keeps their totals."""

    def __init__(self):
        self.seconds: Dict[str, float] = {n: 0.0 for n in NAMES}
        self.counts: Dict[str, int] = {n: 0 for n in NAMES}

    def span(self, name: str, **ids) -> Span:
        """A trace span named ``name`` (one of ``NAMES``), tagged with
        ``ids`` in the profile (per-request spans only)."""
        if name not in self.counts:
            raise KeyError(f"unknown trace span {name!r}")
        return Span(self, name, ids)

    def totals(self) -> Dict[str, float]:
        """``trace.<name>.s`` (seconds) and ``trace.<name>.n`` (count)
        of every trace span since the server started."""
        out: Dict[str, float] = {}
        for n in NAMES:
            out[f"trace.{n}.s"] = self.seconds[n]
            out[f"trace.{n}.n"] = float(self.counts[n])
        return out


__all__ = ["NAMES", "Span", "Tracer"]
