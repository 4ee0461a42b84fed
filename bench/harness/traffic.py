"""The general traffic generator; every mix is a data file it reads.

A mix (``traffic/<name>.json``) is either ``"kind": "sessions"`` (a fixed
set of long requests preloaded in set-up that decode through the window)
or ``"kind": "open"`` (open-loop streams of requests that arrive on a
schedule). A mix this generator cannot express names a generator of its
own, ``"generator": "<module>"``: ``traffic/<module>.py`` with the same
``generate(mix, seed, seconds, vocab)``, found by that name. Sizes are drawn at evenly spaced quantiles, and their order and the
inter-arrival gaps' order come from the mix's own ``order_seed``: every
run seed gets the same requests at the same times, and the run's seed
draws only the token ids (and the weights). So a seed never changes how
much work the window holds or when it comes.

Length distributions: ``{"dist": "fixed", "value"}``,
``{"dist": "uniform", "lo", "hi"}`` and
``{"dist": "lognormal", "median", "sigma", "lo", "hi"}`` (clipped).
Arrival processes: ``{"process": "poisson", "rate_hz"}`` and
``{"process": "periodic", "period_s"}`` (phase from the mix's order).
A stream may also give every request of it a ``deadline_s`` (seconds
after arrival) and a ``priority``.
"""
from __future__ import annotations

import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from harness.spec import BENCH_DIR, load_file

TRAFFIC_DIR = os.path.join(BENCH_DIR, "traffic")


@dataclass(frozen=True)
class Req:
    """One request of the mix: due time (s after the traffic starts),
    prompt token ids, output length, stream index; how it is sampled
    (greedy at temperature 0, the only kind the reference judges), its
    deadline and priority; ``preload``: admitted one at a time in set-up
    (a session that decodes through the window) rather than on time."""
    due: float
    prompt: List[int]
    max_new: int
    stream: int
    temperature: float = 0.0
    deadline_s: Optional[float] = None
    priority: int = 0
    preload: bool = False


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The n lengths of a distribution at evenly spaced quantiles."""
    u = _quantiles(n)
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        x = dist["lo"] + (dist["hi"] - dist["lo"]) * u
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    x = np.rint(x).astype(np.int64)
    if "lo" in dist:
        x = np.clip(x, dist["lo"], dist["hi"])
    return x


def stream_count(stream: Dict[str, Any], span_s: float) -> int:
    """Requests a stream offers over ``span_s`` seconds."""
    arr = stream["arrivals"]
    if arr["process"] == "poisson":
        return int(math.ceil(arr["rate_hz"] * span_s))
    if arr["process"] == "periodic":
        return int(math.ceil(span_s / arr["period_s"]))
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def due_times(arr: Dict[str, Any], n: int,
              rng: np.random.Generator) -> np.ndarray:
    """Arrival times of n requests of one stream, from 0."""
    if arr["process"] == "poisson":
        gaps = rng.permutation(-np.log1p(-_quantiles(n)) / arr["rate_hz"])
        return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    phase = rng.uniform(0.0, arr["period_s"])
    return phase + arr["period_s"] * np.arange(n)


def pairs(stream: Dict[str, Any], n: int) -> np.ndarray:
    """The n (prompt, output) length pairs of a stream: the same pairs
    for every seed (outputs matched to prompts by a fixed shuffle)."""
    new = lengths(stream["output"], n)
    return np.stack([lengths(stream["prompt"], n),
                     new[np.random.default_rng(0).permutation(n)]], 1)


def generate(mix: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Req]:
    """All requests of a mix for a window of ``seconds`` (plus its
    warm-up), in order of their due times."""
    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(mix["order_seed"])
    out: List[Req] = []
    if mix["kind"] == "sessions":
        n = mix["sessions"]
        for p, o in order.permutation(pairs(mix, n)):
            out.append(Req(0.0, rng.integers(0, vocab, p).tolist(), int(o),
                           0, preload=True))
        return out
    span = mix["warmup_s"] + seconds
    for si, stream in enumerate(mix["streams"]):
        n = stream_count(stream, span)
        due = due_times(stream["arrivals"], n, order)
        for t, (p, o) in zip(due, order.permutation(pairs(stream, n))):
            out.append(Req(float(t), rng.integers(0, vocab, p).tolist(),
                           int(o), si, deadline_s=stream.get("deadline_s"),
                           priority=stream.get("priority", 0)))
    out.sort(key=lambda r: r.due)
    return out


def generator(mix: Dict[str, Any]
              ) -> Callable[[Dict[str, Any], int, float, int], List[Req]]:
    """The generator a mix names (``traffic/<generator>.py``), or this
    module's ``generate`` when it names none."""
    name = mix.get("generator")
    if name is None:
        return generate
    return load_file(os.path.join(TRAFFIC_DIR, f"{name}.py"),
                     f"bench_traffic_{name}").generate

