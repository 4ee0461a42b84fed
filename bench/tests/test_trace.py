"""The reduction from a device trace to busy time, program and kernel
time, and named idle gaps: on hand-made intervals, and on a small trace
recorded on a TPU v5e (olmo-1b, four requests, a few decode steps)."""
import os

import pytest

from harness.trace import (DeviceTrace, Trace, clip, intersect, module_base,
                           op_base, reduce_xplane, union)

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_interval_algebra():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert intersect([(0, 3), (5, 8)], [(2, 6)]) == [(2, 3), (5, 6)]


def test_names():
    assert op_base("%paged_micro_attention.5 = (f32[32,16,128]) "
                   "custom-call(s32[32,16] %bitcast.195)") == \
        "paged_micro_attention"
    assert op_base("%fusion = bf16[8] fusion(%p)") == "fusion"
    assert module_base("jit__decode_step_paged_jit(13479831717893519897)") \
        == "_decode_step_paged_jit"


def hand_trace():
    dev = DeviceTrace()
    dev.modules["_decode_step_paged_jit"] += [(10, 40), (50, 80)]
    dev.modules["_sample_batch"] += [(40, 45)]
    dev.ops["paged_micro_attention"] += [(12, 20), (52, 60)]
    dev.ops["while"] += [(11, 39)]
    dev.ops["fusion"] += [(20, 30)]
    spans = [("bench.window", 0, 100), ("bench.step", 5, 48),
             ("bench.step", 48, 85), ("bench.sleep", 85, 100)]
    return Trace([dev], spans, (0, 100))


def test_busy_program_kernel_and_gaps():
    tr = hand_trace()
    assert tr.busy(0) == [(10, 45), (50, 80)]
    assert tr.busy_s() == pytest.approx(65e-9)
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.module_times("_decode_step_paged_jit") == \
        pytest.approx([30e-9, 30e-9])
    assert tr.op_time("paged_micro_attention") == (pytest.approx(16e-9), 2)
    assert [n for n, _ in tr.top_ops()] == ["paged_micro_attention",
                                           "fusion"]
    gaps = tr.idle_gaps()
    assert gaps[0] == ["bench.sleep", pytest.approx(20e-9)]
    assert gaps[1] == ["bench.step", pytest.approx(10e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx(
        [5e-9, 10e-9, 20e-9])
    assert tr.busy(0, within=[(0, 30)]) == [(10, 30)]


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_recorded_tpu_trace():
    tr = reduce_xplane(SMALL)
    assert len(tr.devices) == 1
    steps = tr.module_times("_decode_step_paged_jit")
    chunks = tr.module_times("_prefill_chunk_paged_jit")
    assert steps and chunks
    kt, kn = tr.op_time("paged_micro_attention")
    pt, pn = tr.op_time("paged_prefill_attention")
    # one kernel call per layer (16) in every decode step / prefill chunk
    assert kn == 16 * len(steps) and pn == 16 * len(chunks)
    assert 0 < kt < sum(steps) and 0 < pt < sum(chunks)
    assert 0 < tr.busy_s() < tr.window_s
    assert {g[0] for g in tr.idle_gaps()} <= {"bench.step", "bench.sleep",
                                              "none"}
