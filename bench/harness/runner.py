"""One run of one cell: set-up, warm-up, the measured window, the
comparison with the reference, and the result line.

Set-up builds the weights on the device from the seed, the server (on
the mesh the deployment names, if any), and runs the mix's shape warm-up
(closed loop, every program the window will use), its preloaded
sessions and its traffic warm-up; ``setup_s`` runs from process start
to the window's start. Nothing may compile in the window: the two trace
counters of the program and JAX's own compile events are read around
it; a ``StallWatch`` logs where the host was in any step that stalled.
After the window the device's peak memory is read, the server is
freed, and the reference judges a sample of the requests the window
served.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from harness import reference, traffic
from harness.peaks import peaks
from harness.pump import Pump, Record, StallWatch
from harness.record import RunRecord, load_reader
from harness.spec import BENCH_DIR, Cell

OUT_DIR = os.path.join(BENCH_DIR, ".out")


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    """A progress line on standard output (never the last line)."""
    print(msg, flush=True)


@dataclass
class CompileWatch:
    """Counts JAX's lowerings, backend compiles and compile-cache events,
    in all and inside the window."""
    total: Dict[str, int] = field(default_factory=lambda: dict(
        lowered=0, compiled=0, cache_hits=0, cache_misses=0))
    window: Dict[str, int] = field(default_factory=lambda: dict(
        lowered=0, compiled=0, cache_hits=0, cache_misses=0))
    in_window: bool = False

    def _count(self, key: str) -> None:
        self.total[key] += 1
        if self.in_window:
            self.window[key] += 1

    def install(self) -> None:
        """Register the listeners with ``jax.monitoring``."""
        import jax

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self._count("cache_hits")
            elif event == "/jax/compilation_cache/cache_misses":
                self._count("cache_misses")

        def on_duration(event, _secs, **_):
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self._count("lowered")
            elif event == "/jax/core/compile/backend_compile_duration":
                self._count("compiled")

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)


def program_config(cell: Cell):
    """The program's ModelConfig (from the cell's architecture) and
    ServingConfig for a cell."""
    from repro.serving import ServingConfig
    return (cell.arch.program_config(cell.model),
            serving_config(ServingConfig, cell.serving))


def serving_config(cls, kw: Dict[str, Any]):
    """``cls(**kw)``, where a nested dict fills the dataclass that is its
    field's default (the overload and fault policies)."""
    defaults = {f.name: f.default for f in dataclasses.fields(cls)}
    return cls(**{k: type(defaults[k])(**v)
                  if isinstance(v, dict)
                  and dataclasses.is_dataclass(defaults.get(k)) else v
                  for k, v in kw.items()})


def program_mesh(cell: Cell, devices) -> Tuple[Any, Any]:
    """The (mesh, layout) of the deployment's ``mesh`` entry (``shape``,
    ``axes``, ``batch_axes``, ``pool_axes``), or (None, None)."""
    spec = cell.config["deployment"].get("mesh")
    if spec is None:
        return None, None
    from repro.launch.mesh import make_mesh
    from repro.serving.sharded_step import ServeLayout
    n = math.prod(spec["shape"])
    mesh = make_mesh(spec["shape"], spec["axes"], devices=devices[:n])
    return mesh, ServeLayout(batch_axes=tuple(spec["batch_axes"]),
                             pool_axes=tuple(spec["pool_axes"]))


def engine_totals(server) -> Dict[str, float]:
    """Summed host table-build seconds and decode steps of all engines."""
    engs = server.cluster.engines.values()
    return {"host_gather_s": sum(e.stats.host_gather_s for e in engs),
            "decode_steps": sum(e.stats.decode_steps for e in engs)}


def program_counters(server) -> Dict[str, float]:
    """The numeric entries of the program's ``server.metrics``: its
    counters and trace-span totals (none from a program without it)."""
    counters = getattr(server, "metrics", None) or {}
    return {k: float(v) for k, v in counters.items()
            if isinstance(v, (int, float))}


def trace_counts() -> Tuple[int, int]:
    """The program's (decode step, prefill chunk) trace counters."""
    from repro.models.prefill import (paged_trace_count,
                                      prefill_chunk_trace_count)
    return paged_trace_count(), prefill_chunk_trace_count()


def shape_warmup(pump: Pump, mix: Dict[str, Any], seed: int,
                 vocab: int) -> None:
    """Closed loop, one request at a time: every (prompt, output) length
    pair the mix lists, so each program the window uses is built."""
    rng = np.random.default_rng([int(seed), 1])
    for plen, new in mix.get("shape_warmup", ()):
        req = traffic.Req(0.0, rng.integers(0, vocab, plen).tolist(),
                          int(new), -1)
        rec = pump.submit(req, time.monotonic())
        pump.drain([rec])


def pick_sample(records: List[Record], window, check: Dict[str, Any],
                seed: int) -> List[Record]:
    """Requests to judge: the longest the window served, then others
    drawn from the seed, up to ``check["requests"]``."""
    w0, w1 = window
    if check.get("finished", True):
        cands = [r for r in records if r.state == "FINISHED"
                 and r.finish_time is not None and w0 <= r.finish_time < w1]
    else:
        cands = [r for r in records
                 if any(w0 <= t < w1 for t in r.token_times)]
    cands = [r for r in cands if r.stream >= 0 and r.greedy and r.output]
    if not cands:
        return []
    cands.sort(key=lambda r: (len(r.prompt) + len(r.output), r.due))
    longest = cands.pop()
    rng = np.random.default_rng([int(seed), 2])
    order = rng.permutation(len(cands))
    return [longest] + [cands[i] for i in order[:check["requests"] - 1]]


def start_traffic(pump: Pump, mix: Dict[str, Any],
                  reqs: List[traffic.Req]) -> Tuple[list, int]:
    """Bring the mix to where the window starts: admit the preloaded
    requests one at a time (each placed after the last is admitted, so
    they spread over the instances as they would arrive) and decode each
    for the mix's ``decode_warmup`` tokens; then serve the others that
    fall due in its ``warmup_s``. Returns the (due, request) pairs not
    preloaded and the index of the first not submitted."""
    recs = []
    for r in (r for r in reqs if r.preload):
        recs.append(pump.submit(r, time.monotonic()))
        while not recs[-1].handle._req.output and not recs[-1].handle.done:
            pump.step()
    while recs and not all(r.handle.done for r in recs) and min(
            len(r.handle._req.output) for r in recs) < \
            mix.get("decode_warmup", 0):
        pump.step()
    t_traffic = time.monotonic()
    pending = [(t_traffic + r.due, r) for r in reqs if not r.preload]
    return pending, pump.run(pending, 0,
                             until=t_traffic + mix.get("warmup_s", 0))


def log_window(run: RunRecord, due: List[Record], late: List[float],
               watch: CompileWatch, in_window: Dict[str, int]) -> None:
    """Progress lines about the window: requests, token gaps, how late
    the generator ran, compilations, memory, the trace."""
    w0, w1 = run.window
    log(f"window {w1 - w0:.3f} s; requests due {len(due)}, finished "
        f"{sum(r.state == 'FINISHED' for r in due)}, failed "
        f"{sum(r.state == 'FAILED' for r in due)}; tokens in window "
        f"{len(run.window_tokens())}")
    gaps = run.gaps_s()
    if gaps:
        log("token gaps in the window (ms): " + ", ".join(
            f"p{q} {1e3 * np.percentile(gaps, q):.3f}"
            for q in (50, 90, 95, 99, 100)) +
            f", mean {1e3 * np.mean(gaps):.3f} over {len(gaps)}")
    if late:
        log(f"generator lateness: mean {1e3 * np.mean(late):.3f} ms, max "
            f"{1e3 * np.max(late):.3f} ms over {len(late)} submissions")
    log(f"compile cache: {watch.total['cache_hits']} hits, "
        f"{watch.total['cache_misses']} misses; set-up lowered "
        f"{watch.total['lowered'] - in_window['lowered']}, compiled "
        f"{watch.total['compiled'] - in_window['compiled']}")
    log("compilations inside the window: " + ", ".join(
        f"{k} {v}" for k, v in in_window.items()))
    log(f"peak_bytes_in_use {run.memory['peak_bytes_in_use']} of "
        f"bytes_limit {run.memory['bytes_limit']}")
    kv = [x.kv_tokens for x in run.samples if run.in_window(x.t)]
    if kv:
        log(f"KV tokens of running requests in the window: mean "
            f"{np.mean(kv):.1f}, max {max(kv)} over {len(kv)} steps")
    tr = run.trace
    if tr is not None:
        log(f"trace: busy {tr.busy_s():.6f} s of window {tr.window_s:.6f} s;"
            " longest idle gaps (start in window s, length s): " + ", ".join(
                f"({(s - tr.window[0]) / 1e9:.3f}, {(e - s) / 1e9:.4f})"
                for s, e in tr.gaps()[:3]))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             control: bool = False) -> Dict[str, Any]:
    """Run one cell once; returns the result object (and prints the
    progress lines and the compared numbers on the way). Off the chip
    (``require_tpu=False``, the tests) the compile cache stays off.
    ``control`` also judges the float8 control on the same sample, by the
    same numbers and limits (``result["control"]``: its ``correct`` and
    ``compared``; see ``bench/control.py``)."""
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devices[0].platform!r} "
                     f"({devices[0].device_kind}), not a TPU")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    devices = devices[:cell.chips]
    kind = devices[0].device_kind
    pk = peaks(kind) if require_tpu else {}

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import LLMServer

    from harness.weights import root_key

    cache_dir = "off"
    if require_tpu:
        cache_dir = enable_compile_cache()
        # Keep every program, fast compiles too, so that a warm set-up
        # finds all of them in the cache.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    watch = CompileWatch()
    watch.install()
    m, mix = cell.model, cell.traffic
    log(f"cell {cell.name}: {m.name} ({m.layers} layers, d {m.d_model}), "
        f"traffic {cell.traffic_name}, seed {seed}, {seconds} s, "
        f"trace {int(trace)}; device {kind} x{len(devices)}; compile "
        f"cache {cache_dir}")

    cfg, sc = program_config(cell)
    mesh, layout = program_mesh(cell, devices)
    params = cell.arch.program_params(root_key(seed), m)
    jax.block_until_ready(params)
    server = LLMServer(params, cfg, sc, mesh=mesh, layout=layout)
    pump = Pump(server, sample_steps=trace)
    shape_warmup(pump, mix, seed, m.vocab)
    log(f"shape warm-up done at {time.monotonic() - t_start:.1f} s")

    reqs = traffic.generator(mix)(mix, seed, seconds, m.vocab)
    pending, i = start_traffic(pump, mix, reqs)
    trace_dir = os.path.join(OUT_DIR, "trace", f"{cell.name}-{seed}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = (engine_totals(server), trace_counts(),
              program_counters(server))
    pump.stall_watch = stalls = StallWatch()
    stalls.start()
    w0 = time.monotonic()
    watch.in_window = True
    with jax.profiler.TraceAnnotation("bench.window"):
        pump.run(pending, i, until=w0 + seconds)
    w1 = time.monotonic()
    watch.in_window = False
    stalls.stop()
    after = (engine_totals(server), trace_counts(),
             program_counters(server))
    if trace:
        jax.profiler.stop_trace()
    mem = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in mem)
    limit = min(s.get("bytes_limit", 0) for s in mem)

    for r in pump.records:
        r.freeze()
    records = [r for r in pump.records if r.stream >= 0]
    late = [d for t, d in pump.late if w0 <= t < w1]
    samples = pump.samples
    del pump, server, params, reqs, pending
    gc.collect()

    tr = None
    if trace:
        from harness.trace import reduce_xplane
        path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        tr = reduce_xplane(path)
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = RunRecord(
        arch=cell.arch, model=m, serving=cell.serving, chips=cell.chips,
        window=(w0, w1), setup_s=w0 - t_start, records=records,
        samples=samples,
        engine_delta={k: after[0][k] - before[0][k] for k in before[0]},
        program={"before": before[2], "after": after[2]},
        memory={"peak_bytes_in_use": peak, "bytes_limit": limit},
        peaks=pk, trace=tr)
    metrics = {}
    for spec in cell.metrics(trace):
        v = load_reader(spec["name"])(run)
        if v is not None:
            metrics[spec["name"]] = {"value": float(v), "unit": spec["unit"]}

    due = [r for r in records if r.preload or w0 <= r.due < w1]
    failed = sum(r.state == "FAILED" for r in due)
    in_window = dict(watch.window, decode_traces=after[1][0] - before[1][0],
                     prefill_traces=after[1][1] - before[1][1])
    log_window(run, due, late, watch, in_window)
    log(f"stalled steps in the window: {stalls.summary()}")

    compared, control_compared = judge(cell, seed, records, (w0, w1),
                                       failed, control)
    correct = passes(compared)
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result = {
        "correct": bool(correct),
        "attempted": len(due),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if tr is not None:
        result["device"]["busy_s"] = tr.busy_s()
        result["device"]["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    if control:
        result["control"] = {"correct": passes(control_compared),
                             "compared": control_compared}
    result["compared"] = compared
    return result


def passes(compared: Dict[str, Dict]) -> bool:
    """Whether every number compared lies within its limit."""
    return all(c["value"] <= c["limit"] for c in compared.values())


def judge(cell: Cell, seed: int, records: List[Record], window,
          failed: int, control: bool = False
          ) -> Tuple[Dict[str, Dict], Optional[Dict[str, Dict]]]:
    """The numbers that decide ``correct``, each beside its limit; with
    ``control``, the same numbers for the float8 control in the
    program's place."""
    check = cell.traffic["check"]
    sample = pick_sample(records, window, check, seed)
    seqs = [(r.prompt, r.output) for r in sample]
    t = time.monotonic()
    g = cell.arch.gaps(cell.model, seed, seqs, check["pad_to"],
                       control=control) if seqs else {}
    served = g.get("served")
    n_tok = 0 if served is None else int(served.size)
    log(f"reference: {len(seqs)} requests, {n_tok} served tokens, "
        f"longest {max((len(p) + len(o) for p, o in seqs), default=0)} "
        f"tokens, {time.monotonic() - t:.1f} s")
    limit = cell.config["check"]["max_logit_gap"]

    def compared(gap):
        return {
            "max_logit_gap": {"value": reference.widest(gap),
                              "limit": limit},
            "unchecked": {"value": int(n_tok == 0), "limit": 0},
            "failed_requests": {"value": failed, "limit": 0},
        }
    ctl = compared(g.get("control")) if control else None
    if control:
        log(f"control (float8 reference): widest gap "
            f"{ctl['max_logit_gap']['value']!r}")
    return compared(served), ctl
