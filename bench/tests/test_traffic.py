"""The traffic generator: deterministic in the seed, inside its stated
ranges, and the same work for every seed (only the order changes)."""
import collections
import re

import pytest

from harness import traffic
from harness.spec import load_benchmark, load_cell

CELLS = [w["name"] for w in load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_deterministic_and_in_range(name):
    cell = load_cell(name)
    mix = cell.traffic
    a = traffic.generate(mix, 2**33 + 7, 40, cell.model.vocab)
    b = traffic.generate(mix, 2**33 + 7, 40, cell.model.vocab)
    assert a == b
    streams = mix.get("streams", [mix])
    for r in a:
        s = streams[r.stream]
        for d, n in ((s["prompt"], len(r.prompt)), (s["output"], r.max_new)):
            lo = d.get("lo", d.get("value"))
            hi = d.get("hi", d.get("value"))
            assert lo <= n <= hi
        assert all(0 <= t < cell.model.vocab for t in r.prompt)


@pytest.mark.parametrize("name", CELLS)
def test_same_requests_for_every_seed(name):
    cell = load_cell(name)
    runs = [traffic.generate(cell.traffic, s, 40, cell.model.vocab)
            for s in (1, 2**31 + 5)]
    shapes = [collections.Counter((len(r.prompt), r.max_new, r.stream)
                                  for r in run) for run in runs]
    assert shapes[0] == shapes[1]
    assert [(r.due, len(r.prompt), r.max_new) for r in runs[0]] == \
        [(r.due, len(r.prompt), r.max_new) for r in runs[1]]
    assert runs[0] != runs[1]


def test_poisson_rate_and_span():
    mix = {"kind": "open", "order_seed": 1, "warmup_s": 5, "streams": [{
        "arrivals": {"process": "poisson", "rate_hz": 4.0},
        "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                   "lo": 32, "hi": 1536},
        "output": {"dist": "fixed", "value": 8}}]}
    reqs = traffic.generate(mix, 3, 45, 1000)
    assert len(reqs) == 200
    assert 40 < reqs[-1].due < 60
    lens = sorted(len(r.prompt) for r in reqs)
    assert lens[100] == pytest.approx(512, rel=0.02)


def test_periodic_stream():
    mix = {"kind": "open", "order_seed": 1, "warmup_s": 0, "streams": [{
        "arrivals": {"process": "periodic", "period_s": 12.0},
        "prompt": {"dist": "uniform", "lo": 100, "hi": 200},
        "output": {"dist": "uniform", "lo": 32, "hi": 96}}]}
    reqs = traffic.generate(mix, 11, 40, 50)
    assert len(reqs) == 4
    assert [b.due - a.due for a, b in zip(reqs, reqs[1:])] == \
        pytest.approx([12.0] * 3)


def test_stream_deadline_priority_and_sessions_preload():
    mix = {"kind": "open", "order_seed": 1, "warmup_s": 0, "streams": [{
        "arrivals": {"process": "poisson", "rate_hz": 2.0},
        "prompt": {"dist": "fixed", "value": 16},
        "output": {"dist": "fixed", "value": 8},
        "deadline_s": 1.5, "priority": 2}]}
    reqs = traffic.generate(mix, 5, 10, 100)
    assert {(r.deadline_s, r.priority, r.preload, r.temperature)
            for r in reqs} == {(1.5, 2, False, 0.0)}
    sessions = traffic.generate(load_cell("nemo12b-longdecode").traffic, 5,
                                10, 100)
    assert all(r.preload for r in sessions)


def test_mix_names_a_generator_of_its_own(tmp_path, monkeypatch):
    (tmp_path / "burst.py").write_text(
        "from harness.traffic import Req\n"
        "def generate(mix, seed, seconds, vocab):\n"
        "    return [Req(0.5 * i, [seed % vocab] * 4, mix['out'], 0,\n"
        "                deadline_s=2.0) for i in range(3)]\n")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", str(tmp_path))
    mix = {"generator": "burst", "out": 7}
    reqs = traffic.generator(mix)(mix, 9, 10, 100)
    assert [(r.due, r.max_new, r.deadline_s) for r in reqs] == \
        [(0.0, 7, 2.0), (0.5, 7, 2.0), (1.0, 7, 2.0)]
    assert traffic.generator({"kind": "open"}) is traffic.generate


def test_pump_passes_sampling_deadline_and_priority():
    from harness.pump import Pump

    class Server:
        def submit(self, prompt, sampling, **kw):
            self.got = (sampling.max_new_tokens, sampling.temperature, kw)
            return object()

    server = Server()
    rec = Pump(server).submit(
        traffic.Req(0.0, [1, 2], 5, 0, temperature=0.7, deadline_s=3.0,
                    priority=1), 12.5)
    assert server.got == (5, 0.7, {"priority": 1, "deadline_s": 3.0,
                                   "arrival_time": 12.5})
    assert not rec.greedy and not rec.preload


def test_stall_watch_samples_where_a_long_step_waits():
    import time as _time

    from harness.pump import Pump, StallWatch

    class Server:
        def step(self):
            _time.sleep(0.3)

    pump = Pump(Server())
    pump.stall_watch = watch = StallWatch(0.1, 0.02)
    watch.start()
    pump.step()
    watch.stop()
    assert len(watch.stalls) == 1 and watch.stalls[0][1] >= 0.3
    assert any("step" in w for w in watch.where)
    assert "1 steps over 0.1 s" in watch.summary()
    assert re.search(r"; \d+ of 1\d samples due", watch.summary())
