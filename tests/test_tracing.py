"""Trace spans of the serving loop (``repro.serving.tracing``).

The tracer's totals count with no profiler running; one server step
records the loop's spans and ``server.metrics`` reports them; spans feed
the counters they replaced (``host_gather_s``, ``sync_wait_s``);
``Request.admitted_at`` lies between arrival and the first token and
survives a pause; KV moves are traced; and a profile taken around a step
holds the ``serve.*`` host events inside the caller's own annotation.
"""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.model import init_params
from repro.serving import (Cluster, LLMServer, Request, RequestState,
                           SamplingParams, ServingConfig, Tracer)
from repro.serving.config import OverloadPolicy
from repro.serving.staging import AsyncStager
from repro.serving.tracing import NAMES

LOOP_SPANS = ("serve.step", "serve.dispatch", "serve.heartbeat",
              "serve.engine", "serve.admit", "serve.build", "serve.decode",
              "serve.sample", "serve.readback", "serve.drain")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("olmo-1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, n).tolist()


def test_tracer_nests_and_counts_with_no_profiler():
    tr = Tracer()
    with tr.span("serve.step") as outer:
        for i in range(3):
            with tr.span("serve.engine", inst=i) as inner:
                time.sleep(0.002)
            assert inner.seconds >= 0.002
    assert tr.counts["serve.step"] == 1 and tr.counts["serve.engine"] == 3
    assert outer.seconds == tr.seconds["serve.step"]
    assert tr.seconds["serve.step"] >= tr.seconds["serve.engine"] >= 0.006
    totals = tr.totals()
    assert set(totals) == {f"trace.{n}.{k}" for n in NAMES for k in "sn"}
    assert totals["trace.serve.engine.n"] == 3.0
    assert totals["trace.serve.move.s"] == 0.0
    with pytest.raises(KeyError):
        tr.span("serve.nowhere")


def test_stager_wait_is_its_sync_span():
    tr = Tracer()
    stager = AsyncStager(overlap=False, tracer=tr)
    for _ in range(3):
        stager.stage(jnp.ones(4) * 2, tag="spill")
    assert tr.counts["serve.sync"] == stager.synced == 3
    assert stager.sync_wait_s == tr.seconds["serve.sync"]


class _InFlight(np.ndarray):
    """A host copy that reports itself still in flight."""

    def is_ready(self):
        return False


def test_host_tier_stall_is_a_sync_span():
    from repro.serving.hosttier import HostKVTier
    tr = Tracer()
    tier = HostKVTier(4, tracer=tr)
    k = np.ones((2, 4, 1, 8), np.float32).view(_InFlight)
    tier._pending["frame"] = (k, k * 2)
    tier._touch("frame")
    got = tier.get("frame")
    assert got is not None and float(got[1].sum()) == 2 * k.size
    assert tier.stats.fetch_stalls == 1 and tr.counts["serve.sync"] == 1
    assert tier.get("frame") is not None          # resident: no stall
    assert tier.stats.fetch_stalls == 1 and tr.counts["serve.sync"] == 1


def test_server_step_records_the_loop_spans(setup):
    cfg, params = setup
    server = LLMServer(params, cfg, ServingConfig.smoke())
    h = server.submit(_prompt(cfg, 12, 1), SamplingParams(max_new_tokens=6))
    server.step()
    tr = server.tracer
    assert tr is server.cluster.tracer
    for name in LOOP_SPANS:
        assert tr.counts[name] >= 1, name
    assert tr.counts["serve.step"] == 1 and tr.counts["serve.admit"] == 1
    # The admission's first token and the decode step's: two readbacks.
    assert tr.counts["serve.readback"] == 2
    engs = server.cluster.engines.values()
    assert tr.counts["serve.build"] == sum(e.stats.decode_steps
                                           for e in engs)
    assert sum(e.stats.host_gather_s for e in engs) == pytest.approx(
        tr.seconds["serve.build"])
    m = server.metrics
    for name in NAMES:
        assert m[f"trace.{name}.n"] == float(tr.counts[name])
        assert m[f"trace.{name}.s"] == tr.seconds[name]
    req = h._req
    assert req.arrival_time <= req.admitted_at <= req.token_times[0]
    h.result()
    assert tr.counts["serve.admit"] == 1


def test_pause_and_resume_keep_admitted_at(setup):
    cfg, params = setup
    server = LLMServer(params, cfg, ServingConfig.smoke(
        overload=OverloadPolicy(enabled=True)))
    h = server.submit(_prompt(cfg, 12, 2), SamplingParams(max_new_tokens=10))
    req = h._req
    for _ in range(3):
        server.step()
    stamp = req.admitted_at
    assert stamp is not None and req.state == RequestState.RUNNING
    assert server.cluster.preemptor.pause(req)
    h.result()
    assert req.preemptions == 1 and req.state == RequestState.FINISHED
    assert req.admitted_at == stamp


def test_kv_moves_are_traced(setup):
    cfg, params = setup
    cl = Cluster(params, cfg, ServingConfig.smoke(max_batch=2,
                                                  pool_blocks=32))
    req = Request(prompt=_prompt(cfg, 20, 3),
                  sampling=SamplingParams(max_new_tokens=24))
    cl.submit(req)
    cl.run_until_done(max_steps=100)
    assert req.state == RequestState.FINISHED
    assert sum(e.stats.moves for e in cl.engines.values()) >= 1
    # One span per move attempted, executed or refused.
    assert cl.tracer.counts["serve.move"] >= 1
    assert cl.tracer.seconds["serve.move"] > 0
    assert cl.tracer.counts["serve.step"] == 0     # no frontend here


def test_profile_holds_serve_spans_inside_the_callers_span(setup, tmp_path):
    cfg, params = setup
    server = LLMServer(params, cfg, ServingConfig.smoke())
    server.submit(_prompt(cfg, 12, 4), SamplingParams(max_new_tokens=4))
    server.step()          # compile outside the profile
    server.submit(_prompt(cfg, 12, 5), SamplingParams(max_new_tokens=4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.step"):
            server.step()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    events = [ev for pl in jax.profiler.ProfileData.from_file(path).planes
              if pl.name.startswith("/host:")
              for ln in pl.lines for ev in ln.events]
    outer = [ev for ev in events if ev.name == "bench.step"]
    assert len(outer) == 1
    lo, hi = outer[0].start_ns, outer[0].start_ns + outer[0].duration_ns
    serve = [ev for ev in events if ev.name.startswith("serve.")]
    assert {"serve.step", "serve.engine", "serve.admit", "serve.build",
            "serve.decode", "serve.readback"} <= {ev.name for ev in serve}
    assert all(lo <= ev.start_ns and ev.start_ns + ev.duration_ns <= hi
               for ev in serve)
    admit = next(ev for ev in serve if ev.name == "serve.admit")
    ids = {k: v for k, v in admit.stats}
    assert ids["tokens"] == 12 and ids["chunks"] == 2
