"""What a run leaves for the metric readers, and the arithmetic they share.

Every metric is a file ``metrics/<name>.py`` with one function
``read(run: RunRecord) -> float | None``; ``None`` means the run had
nothing to read and the metric is left out of the result line. Besides
the requests, the step samples and the trace, a reader sees every
numeric entry of the program's ``server.metrics`` (counters, and the
serving loop's trace-span totals ``trace.<name>.s`` / ``.n``) as read
just before and just after the window (``RunRecord.program_delta``):
a counter that the program adds reaches a new reader with no edit here.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from harness.pump import Record, StepSample
from harness.spec import BENCH_DIR, load_file
from harness.trace import Trace, to_trace_clock, union

DECODE_STEP = "_decode_step_paged_jit"
PREFILL_CHUNK = "_prefill_chunk_paged_jit"
DECODE_KERNEL = "paged_micro_attention"
PREFILL_KERNEL = "paged_prefill_attention"


@dataclass
class RunRecord:
    """One run: the cell, the window, the requests and the trace;
    ``arch`` is the cell's architecture module and ``model`` its spec."""
    arch: ModuleType
    model: Any
    serving: Dict[str, Any]
    chips: int
    window: Tuple[float, float]          # monotonic (start, end)
    setup_s: float
    records: List[Record]
    samples: List[StepSample] = field(default_factory=list)
    engine_delta: Dict[str, float] = field(default_factory=dict)
    program: Dict[str, Dict[str, float]] = field(default_factory=dict)
    memory: Dict[str, int] = field(default_factory=dict)
    peaks: Dict[str, Any] = field(default_factory=dict)
    trace: Optional[Trace] = None

    # --- requests and tokens ------------------------------------------ #
    def in_window(self, t: float) -> bool:
        """Whether monotonic time ``t`` lies in the window."""
        return self.window[0] <= t < self.window[1]

    def window_tokens(self) -> List[Tuple[Record, int, float]]:
        """(record, output index, time) of every token emitted in the
        window."""
        return [(r, i, t) for r in self.records
                for i, t in enumerate(r.token_times)
                if self.in_window(t)]

    def gaps_s(self) -> List[float]:
        """Gaps between consecutive tokens of one request, for every
        token emitted in the window after the request's first."""
        out = []
        for r in self.records:
            tt = r.token_times
            out += [tt[i] - tt[i - 1] for i in range(1, len(tt))
                    if self.in_window(tt[i])]
        return out

    def ttfts_s(self) -> List[float]:
        """Time to first token of every request due in the window, from
        its due time: a request with no token yet counts at its wait so
        far, a failed one as infinite."""
        w1 = self.window[1]
        out = []
        for r in self.records:
            if not self.in_window(r.due):
                continue
            tt = r.token_times
            if r.state == "FAILED" and not tt:
                out.append(float("inf"))
            elif tt and tt[0] < w1:
                out.append(tt[0] - r.due)
            else:
                out.append(w1 - r.due)
        return out

    def program_delta(self, key: str) -> Optional[float]:
        """How much the program's ``server.metrics`` entry ``key`` grew
        over the window (``program["before"]`` to ``["after"]``); None
        where the program does not report it."""
        before, after = (self.program.get(k, {}) for k in ("before", "after"))
        if key not in before or key not in after:
            return None
        return after[key] - before[key]

    # --- work counted from shapes -------------------------------------- #
    def window_work(self) -> Dict[str, float]:
        """Model operations of the window's tokens, and the decode and
        prefill attention kernels' (operations, bytes).

        A prompt is prefilled in the step that emits its first token, so
        it counts in the window when that token does; output token i
        >= 1 comes from a decode step over ``prompt + i`` tokens. A
        context is held in as many pools as its local quotas need."""
        a, m, bs = self.arch, self.model, self.serving["block_size"]
        C = self.serving["prefill_chunk"]
        cap = self.serving["max_local_len"] - bs
        out = dict(model_flops=0.0, decode_flops=0.0, decode_bytes=0.0,
                   prefill_flops=0.0, prefill_bytes=0.0, prompt_tokens=0,
                   decode_tokens=0)
        for r, i, _t in self.window_tokens():
            T = len(r.prompt)
            if i == 0:
                out["model_flops"] += a.prompt_flops(m, T)
                out["prompt_tokens"] += T
                spans = -(-T // cap)
                for t0 in range(0, T, C):
                    f, b = a.prefill_attn_work(
                        m, min(C, T - t0), t0, spans)
                    out["prefill_flops"] += f
                    out["prefill_bytes"] += b
            else:
                ctx = T + i
                out["model_flops"] += a.token_flops(m, ctx, True)
                out["decode_tokens"] += 1
                spans = -(-ctx // cap)
                f, b = a.decode_attn_work(m, ctx, spans)
                out["decode_flops"] += f
                out["decode_bytes"] += b
        return out

    # --- trace ---------------------------------------------------------- #
    def in_system(self) -> List[Tuple[float, float]]:
        """Trace-clock intervals with at least one request submitted and
        not yet done."""
        tr = self.trace
        anchor = self.window[0]
        ivs = []
        for r in self.records:
            end = r.finish_time if r.finish_time else self.window[1]
            s = to_trace_clock(r.submitted, anchor, tr.window[0])
            e = to_trace_clock(end, anchor, tr.window[0])
            if e > s:
                ivs.append((s, e))
        return union(ivs)

    def roofline(self, kernel: str, flops: float, nbytes: float
                 ) -> Optional[float]:
        """Share (%) of a kernel's summed device time that its least
        time (operations at peak, or bytes at HBM bandwidth) would take."""
        if self.trace is None:
            return None
        t, n = self.trace.op_time(kernel)
        if n == 0 or t <= 0 or (flops <= 0 and nbytes <= 0):
            return None
        least = max(flops / self.peaks["bf16_flops_per_s"],
                    nbytes / self.peaks["hbm_bytes_per_s"])
        return 100.0 * least / t


def percentile(xs: List[float], q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation), None for no samples."""
    return float(np.percentile(np.asarray(xs, float), q)) if xs else None


def load_reader(name: str) -> Callable[[RunRecord], Optional[float]]:
    """The ``read`` function of ``metrics/<name>.py``."""
    return load_file(os.path.join(BENCH_DIR, "metrics", f"{name}.py"),
                     f"bench_metric_{name.replace('.', '_')}").read
