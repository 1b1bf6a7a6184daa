"""trace_reduce: the interval arithmetic on synthetic events, and the
whole reduction on a small trace recorded on the chip (four v5e chips,
benchmark/tests/record_fixture.py, PR 22)."""
from __future__ import annotations

import gzip
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]

from harness import trace_reduce as tr  # noqa: E402


def test_union_total_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7)]
    assert tr.total([(0, 4), (5, 7)]) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [
        (0, 2), (3, 5), (7, 9)]
    assert tr.subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]
    assert tr.subtract([(2, 3)], [(0, 10)]) == []


def test_labels_and_opcodes():
    name = ("%convert_reduce_fusion.12 = f32[64]{0:T(128)} fusion("
            "bf16[256,112,112,64]{3,0,2,1} %p), kind=kInput")
    assert tr.label(name) == "convert_reduce_fusion"
    assert not tr.collective_kind(name)
    assert tr.collective_kind(
        "%all-reduce-start.3 = (bf16[8]{0}, bf16[8]{0}) all-reduce-start("
        "bf16[8]{0} %x), replica_groups={{0,1,2,3}}") == (
            "all-reduce", "start")
    assert tr.collective_kind(
        "%all-reduce-done.3 = bf16[8]{0} all-reduce-done(%all-reduce-start"
        ".3)") == ("all-reduce", "done")
    assert tr.collective_kind(
        "%ar = f32[4]{0} all-reduce(f32[4]{0} %y), to_apply=%add") == (
            "all-reduce", "")
    # an op that only consumes a collective's result is not one
    assert not tr.collective_kind(
        "%fusion.9 = f32[4]{0} fusion(f32[4]{0} %all-reduce.7), kind=kLoop")


def _op(s, e, name="%fusion.1 = f32[1]{0} fusion(f32[1]{0} %p)"):
    return (s, e, name)


START = "%all-reduce-start.1 = f32[4]{0} all-reduce-start(f32[4]{0} %g)"
DONE = "%all-reduce-done.1 = f32[4]{0} all-reduce-done(%all-reduce-start.1)"
SYNC = "%all-reduce.2 = f32[4]{0} all-reduce(f32[4]{0} %s), to_apply=%add"


def test_busy_idle_and_exposed_collective_time_on_synthetic_steps():
    """Two counted steps of 100 ns after one lead-in step.  In each: a
    fusion 0-40, an async all-reduce whose start runs 40-42 and done
    70-80 with a fusion 42-70 under it, a synchronous all-reduce 80-90,
    idle 90-100 (the gap before the next step)."""
    ops, modules = [], []
    for k in range(3):
        t = 100 * k
        ops += [_op(t, t + 40), (t + 40, t + 42, START), _op(t + 42, t + 70),
                (t + 70, t + 80, DONE), (t + 80, t + 90, SYNC)]
        modules.append((t, t + 90, "jit_pure_step(1)"))
        modules.append((t + 95, t + 96, "jit_convert_element_type(2)"))
    spans = {"bench.step_call": [(0, 5), (100, 105), (200, 205)],
             "bench.block": [(5, 100), (105, 200), (205, 290)]}
    t = tr.from_events({0: ops}, {0: modules}, spans, steps=2)
    assert t.window == (90, 290) and t.window_ns == 200
    chip = t.chips[0]
    assert chip.busy_ns == 180
    # async: start 40 to done 80 = 40 a step; sync 10 a step
    assert chip.collective_ns == 100
    # exposed: start 2 + done 10 + sync 10 a step (the fusion hides 28)
    assert chip.collective_exposed_ns == 44
    assert t.busy_s == 180e-9
    b = t.breakdown()
    assert b["device_ops"][0][0] == "fusion"
    assert b["idle_gaps"][0] == ["bench.block", 10e-9]
    assert len(b["idle_gaps"]) == 2


def test_too_few_step_executions_is_an_error_and_no_device_is_none():
    with pytest.raises(ValueError, match="executions"):
        tr.from_events({0: [_op(0, 1)]}, {0: [(0, 1, "jit_pure_step(1)")]},
                       {}, steps=1)
    assert tr.from_events({}, {}, {}, steps=8) is None


# ---- the recorded trace ---------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """benchmark/tests/data/dp4_small.xplane.pb.gz: 2 lead-in + 3 counted
    steps of record_fixture.py's conv + BatchNorm + dense net at batch
    256 over dp=4, on four v5e chips (chiprun --chips 4, PR 22)."""
    path = tmp_path_factory.mktemp("trace") / "dp4_small.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "dp4_small.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return str(path)


def test_recorded_trace_gives_the_numbers_read_from_it_by_hand(recorded):
    assert os.path.getsize(
        os.path.join(HERE, "data", "dp4_small.xplane.pb.gz")) < 1 << 20
    planes = tr.describe(recorded)
    assert {"/device:TPU:0", "/device:TPU:1", "/device:TPU:2",
            "/device:TPU:3", "/host:CPU"} <= set(planes)
    assert {"XLA Ops", "XLA Modules"} <= set(planes["/device:TPU:0"])

    t = tr.reduce(recorded, steps=3)
    assert sorted(t.chips) == [0, 1, 2, 3]
    assert t.steps == 3 and t.window_ns == 16255462.0
    chip = t.chips[0]
    # a tiny net: the device is busy 8.7% of the window, the rest is the
    # host dispatching (the gaps fall inside bench.step_call)
    assert chip.busy_ns == 1415986.0
    assert abs(100 * (1 - chip.busy_ns / t.window_ns) - 91.289) < 1e-3
    # three synchronous all-reduces a step (the BatchNorm statistics and
    # the gradients), nothing asynchronous: all of it exposed
    assert [k for _s, _e, k in chip.collectives] == ["all-reduce"] * 9
    assert chip.collective_ns == chip.collective_exposed_ns == 35237.0
    assert [round(t.chips[i].busy_ns) for i in (1, 2, 3)] == [
        1403936, 1403836, 1401213]
    assert abs(t.busy_s - 1.40624275e-3) < 1e-9
    assert len(t.spans["bench.step_call"]) == 5
    assert len(t.spans["bench.block"]) == 5
    b = t.breakdown()
    assert [name for name, _ in b["device_ops"][:3]] == [
        "fusion", "convert_reduce_fusion", "all-reduce"]
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 5
    assert b["idle_gaps"][0] == ["bench.step_call", 0.003089694]


def test_layer_metric_readers_on_the_recorded_trace(recorded):
    from harness import lookup, peaks

    run = {"trace": tr.reduce(recorded, steps=3), "chips": 4,
           "samples_per_step": 256, "flops_per_sample": 1e9,
           "peak": peaks.peak("TPU v5 lite"), "step_compile_s": 1.5,
           "memory_peak_bytes": 3 * 2**30}

    def read(name):
        return lookup.metric_reader("layer_metrics", name)(run)

    assert abs(read("device_step_ms") - 1415986.0 / 3 / 1e6) < 1e-12
    assert abs(read("device_idle_pct") - 91.289) < 1e-3
    assert abs(read("collective_ms") - 35237.0 / 3 / 1e6) < 1e-12
    assert read("collective_exposed_ms") == read("collective_ms")
    floor_s = 1e9 * 256 / (4 * 197e12)
    assert abs(read("flops_roofline_pct")
               - 100 * floor_s / (1415986.0 / 3 / 1e9)) < 1e-9
    assert 2.0 < read("host_step_ms") < 5.0
    assert read("hbm_peak_gib") == 3.0 and read("step_compile_s") == 1.5
    # nothing to read: nothing returned, and the harness leaves it out
    run["trace"] = None
    for name in ("device_step_ms", "device_idle_pct", "collective_ms",
                 "collective_exposed_ms", "flops_roofline_pct",
                 "host_step_ms"):
        assert read(name) is None
