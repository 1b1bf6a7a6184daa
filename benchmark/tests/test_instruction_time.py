"""instruction_time: chip 0's busy time by kind of work and by pass, and
the FLOPs the step executes, on synthetic events with a hand-made table,
and on a small pair recorded on the chip (one v5e chip,
benchmark/tests/record_instruction_fixture.py, PR 51): the trace and the
`step_programs()` of the process that made it, `instructions` included."""
from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]

from harness import instruction_time, lookup, peaks  # noqa: E402
from harness import trace_reduce as tr  # noqa: E402

STEP = "jit(mx_train_step)/"
READERS = ("matmul_fusion_device_ms", "matmul_fusion_roofline_pct",
           "wgrad_update_device_ms", "wgrad_update_roofline_pct",
           "kernel_device_ms", "kernel_calls_a_step", "vector_device_ms",
           "recompute_device_ms")
PEAK = peaks.peak("TPU v5 lite")


def test_the_eight_entries_are_in_the_manifest_by_name():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    for name in READERS:
        entry = entries[name]
        assert entry["moves"] == "throughput"
        assert entry["source"] == "device_trace"
        assert entry["better"] == (
            "higher" if name.endswith("_roofline_pct") else "lower")
        assert entry["unit"] == ("%" if name.endswith("_pct") else "calls"
                                 if name.endswith("a_step") else "ms")
        assert callable(lookup.metric_reader("layer_metrics", name))
        # every cell reads all eight: a metric without `workloads` has to
        # be in every cell's line, so a step without kernels or without
        # remat reads 0 and not nothing
        assert "workloads" not in entry
    assert len(cells) >= 11


def _record(flops=0, kernel=None, scopes=(), booked=None):
    scopes = [STEP + s for s in scopes]
    return {"opcode": "custom-call" if kernel else "fusion",
            "scopes": scopes, "flops": flops, "kernel": kernel,
            "pass": instruction_pass(booked if booked is not None
                                     else (scopes[0] if scopes else "")),
            "passes": sorted({instruction_pass(s) for s in scopes})
            or ["other"]}


def instruction_pass(name):
    """The program's rule (`parallel.spmd._pass_of`), for the hand-made
    table."""
    for mark, found in (("rematted_computation", "recomputed"),
                        ("transpose(", "backward"), ("jvp(", "forward"),
                        ("mx.update", "update")):
        if mark in name:
            return found
    return "other"


WGRAD = "transpose(jvp(net))/dense0/FullyConnected"
AGAIN = "transpose(jvp(net))/l0/checkpoint/rematted_computation/"
PROGRAM = {"module": "jit_mx_train_step", "origin": "compiled",
           "scoped": True, "ops": {}, "instructions": {
               "fusion.1": _record(1000, scopes=["jvp(net)/dense0/"
                                                 "FullyConnected"]),
               "fusion.2": _record(scopes=["jvp(net)/bn0/BatchNorm"]),
               "attn.3": _record(kernel="attn", scopes=[
                   "jvp(net)/attn/dot_product_attention"]),
               "fusion.4": _record(1000, scopes=[AGAIN + "FullyConnected"]),
               "fusion.5": _record(2000, scopes=[
                   "mx.update/adam_update", WGRAD], booked=STEP + WGRAD),
               "fusion.6": _record(scopes=["mx.update/adam_update"]),
               "while.7": _record(),
               "fusion.8": _record(300, scopes=[
                   "transpose(jvp(net))/while/body/dense1/FullyConnected"]),
               "attn.9": _record(kernel="attn", scopes=[
                   AGAIN + "dot_product_attention"]),
               "copy.10": _record()}}


def _ev(s, e, lhs, opcode="fusion"):
    return (s, e, f"%{lhs} = f32[4]{{0}} {opcode}(f32[4]{{0}} %p)")


def _synthetic(steps=2):
    """One lead-in and `steps` counted steps of 100 ns: a product 0-20,
    vector work 20-30, a kernel 30-40, the product again under remat
    40-50, a weight gradient with Adam behind it 50-70, the update's own
    kernel 70-74, a `while` 74-90 whose body runs a product twice (76-80,
    82-86), the kernel again under remat 90-93, a copy 93-95, and between
    two steps a tiny program whose one instruction shares the name
    `fusion.1`."""
    ops, modules = [], []
    for k in range(steps + 1):
        t = 100 * k
        ops += [_ev(t, t + 20, "fusion.1"), _ev(t + 20, t + 30, "fusion.2"),
                _ev(t + 30, t + 40, "attn.3", "custom-call"),
                _ev(t + 40, t + 50, "fusion.4"),
                _ev(t + 50, t + 70, "fusion.5"),
                _ev(t + 70, t + 74, "fusion.6"),
                _ev(t + 74, t + 90, "while.7", "while"),
                _ev(t + 76, t + 80, "fusion.8"),
                _ev(t + 82, t + 86, "fusion.8"),
                _ev(t + 90, t + 93, "attn.9", "custom-call"),
                _ev(t + 93, t + 95, "copy.10", "copy"),
                _ev(t + 97, t + 99, "fusion.1")]
        modules += [(t, t + 95, "jit_mx_train_step(1)"),
                    (t + 97, t + 99, "jit__unstack(2)")]
    trace = tr.from_events({0: ops}, {0: modules}, {}, steps=steps)
    return trace, modules


def test_kind_of_an_instruction_first_match():
    table = PROGRAM["instructions"]
    assert {n: instruction_time.kind(r) for n, r in table.items()} == {
        "fusion.1": "matmul_fusion", "fusion.2": "vector",
        "attn.3": "kernel", "fusion.4": "matmul_fusion",
        "fusion.5": "wgrad_update", "fusion.6": "vector",
        "while.7": "vector", "fusion.8": "matmul_fusion",
        "attn.9": "kernel", "copy.10": "vector"}
    # a kernel that held a product would still be a kernel
    assert instruction_time.kind(_record(5, kernel="gmm")) == "kernel"


def test_synthetic_steps_split_by_kind_and_by_pass():
    from harness import scope_time

    trace, modules = _synthetic()
    assert trace.window == (95, 295) and trace.chips[0].busy_ns == 194
    _name, runs = scope_time.step_module(modules)
    it = instruction_time.attribute(trace, PROGRAM, step_runs=runs)
    # the `while` keeps what its body does not cover; the foreign
    # `fusion.1` between two steps is vector work whatever its name
    assert it.kind_ns == {"matmul_fusion": 2 * (20 + 10 + 8),
                          "wgrad_update": 2 * 20, "kernel": 2 * 13,
                          "vector": 2 * (10 + 4 + 8 + 2 + 2)}
    assert sum(it.kind_ns.values()) == it.busy_ns == 194
    assert it.kind_flops == {"matmul_fusion": 2 * (1000 + 1000 + 2 * 300),
                             "wgrad_update": 2 * 2000, "kernel": 0,
                             "vector": 0}
    assert it.pass_ns == {"forward": 2 * 40, "recomputed": 2 * 13,
                          "backward": 2 * 28, "update": 2 * 4,
                          "other": 2 * (8 + 2 + 2)}
    assert sum(it.pass_ns.values()) == it.busy_ns
    assert it.pass_flops["recomputed"] == 2 * 1000
    assert it.kernel_calls == {"attn": 4}
    assert it.kernel_instances == {"attn": 2}
    assert it.missing_ns == 0
    assert it.dearest[0][1:3] == ("fusion.1", 2)
    # FLOP/ns over FLOP/s: 2,600 FLOP in 38 ns a step at a peak of 1e11
    assert it.roofline_pct("matmul_fusion", 1e11) == pytest.approx(
        100 * 2600 / 38 / 100)
    assert it.roofline_pct("kernel", 1e11) == 0.0
    report = it.report()
    assert report["kind_ms"]["wgrad_update"] == 20e-6
    assert report["kernel_calls_a_step"] == {"attn": 2.0}
    assert report["dearest"][0]["scopes"] == [
        STEP + "jvp(net)/dense0/FullyConnected"]
    assert json.dumps(report)
    # without the module runs the foreign `fusion.1` reads as a product
    loose = instruction_time.attribute(trace, PROGRAM)
    assert loose.kind_ns["matmul_fusion"] == 2 * 38 + 2 * 2
    assert loose.kind_flops["matmul_fusion"] == 2 * 2600 + 2 * 1000
    # an instruction the table does not hold is vector work, and counted
    partial = dict(PROGRAM, instructions={
        k: v for k, v in PROGRAM["instructions"].items() if k != "fusion.2"})
    it = instruction_time.attribute(trace, partial, step_runs=runs)
    assert it.missing_ns == 20 and sum(it.kind_ns.values()) == 194


def _through_the_readers(monkeypatch, trace, programs, path=None):
    monkeypatch.setattr(instruction_time, "_programs", lambda: programs)
    monkeypatch.setattr(
        tr, "newest_xplane", lambda _dir: path if path is not None else
        (_ for _ in ()).throw(FileNotFoundError(_dir)))
    run = {"trace": trace, "chips": 1, "samples_per_step": 8, "peak": PEAK}
    return {name: lookup.metric_reader("layer_metrics", name)(run)
            for name in READERS}


def test_readers_on_synthetic_steps(monkeypatch, capsys):
    trace, _modules = _synthetic()
    got = _through_the_readers(monkeypatch, trace, [PROGRAM])
    # no file: no module runs, so the foreign op reads as a product
    assert got == {
        "matmul_fusion_device_ms": 40e-6,
        "matmul_fusion_roofline_pct": pytest.approx(
            100 * 3600 / 40e-9 / PEAK.flops_bf16),
        "wgrad_update_device_ms": 20e-6,
        "wgrad_update_roofline_pct": pytest.approx(
            100 * 2000 / 20e-9 / PEAK.flops_bf16),
        "kernel_device_ms": 13e-6, "kernel_calls_a_step": 2.0,
        "vector_device_ms": 24e-6, "recompute_device_ms": 13e-6}
    assert got["matmul_fusion_device_ms"] + got["wgrad_update_device_ms"] \
        + got["kernel_device_ms"] + got["vector_device_ms"] \
        == pytest.approx(lookup.metric_reader(
            "layer_metrics", "device_step_ms")({"trace": trace}))
    # computed once for the eight of them, and said once
    assert capsys.readouterr().out.count('"instruction_time"') == 1


def test_a_step_without_kernels_or_remat(monkeypatch):
    """No kernel reads 0 ms and 0 calls, no recomputed pass 0 ms: the
    metrics are in every cell's line."""
    plain = dict(PROGRAM, instructions={
        name: (_record(scopes=["jvp(net)/attn/dot_product_attention"])
               if r["kernel"] else
               _record(1000, scopes=["transpose(jvp(net))/l0/FullyConnected"])
               if r["pass"] == "recomputed" else r)
        for name, r in PROGRAM["instructions"].items()})
    got = _through_the_readers(monkeypatch, _synthetic()[0], [plain])
    assert got["kernel_device_ms"] == 0.0
    assert got["kernel_calls_a_step"] == 0.0
    assert got["recompute_device_ms"] == 0.0
    # nor does a step whose update rides no weight gradient (dp=4)
    apart = dict(plain, instructions={
        name: dict(r, passes=["backward"]) if r["flops"] else r
        for name, r in plain["instructions"].items()})
    got = _through_the_readers(monkeypatch, _synthetic()[0], [apart])
    assert got["wgrad_update_device_ms"] == 0.0
    assert got["wgrad_update_roofline_pct"] == 0.0
    assert got["matmul_fusion_device_ms"] == pytest.approx(60e-6)
    assert got["vector_device_ms"] == pytest.approx(37e-6)


def _old_table():
    with open(os.path.join(HERE, "data", "scope_small.programs.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("programs", [
    "the parent's table", [], [dict(PROGRAM, scoped=False)],
    # the newest program decides
    [PROGRAM, dict(PROGRAM, scoped=False)],
    [dict(PROGRAM, module="jit_other")]])
def test_a_table_without_instructions_reads_as_nothing(
        monkeypatch, tmp_path, capsys, programs):
    """`scope_small.programs.json` as PR 24 recorded it is what the parent
    of PR 51 hands out: every reader leaves its metric out, and nothing
    is said."""
    if programs == "the parent's table":
        programs = _old_table()
        assert all("instructions" not in p for p in programs)
    path = None
    if programs and programs[0]["module"] == "jit_other":
        path = tmp_path / "scope_small.xplane.pb"
        with gzip.open(os.path.join(
                HERE, "data", "scope_small.xplane.pb.gz")) as f:
            path.write_bytes(f.read())
        path = str(path)
    trace, _modules = _synthetic()
    got = _through_the_readers(monkeypatch, trace, programs, path)
    assert got == dict.fromkeys(READERS)
    assert "instruction_time" not in capsys.readouterr().out
    assert _through_the_readers(monkeypatch, None, [PROGRAM]) \
        == dict.fromkeys(READERS)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """benchmark/tests/data/instruction_small.*: 2 lead-in + 3 counted
    steps of record_instruction_fixture.py's encoder layer and dense head
    under Adam with remat on, at batch 16 on one v5e chip (PR 51), and the
    step_programs() of that process, `instructions` included."""
    data = os.path.join(HERE, "data")
    path = tmp_path_factory.mktemp("trace") / "instruction_small.xplane.pb"
    with gzip.open(os.path.join(
            data, "instruction_small.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with gzip.open(os.path.join(
            data, "instruction_small.programs.json.gz"), "rt") as f:
        programs = json.load(f)
    return str(path), programs


def test_recorded_pair_adds_up_to_the_busy_time_to_the_nanosecond(recorded):
    from harness import scope_time

    path, programs = recorded
    data = os.path.join(HERE, "data")
    assert sum(os.path.getsize(os.path.join(data, f)) for f in (
        "instruction_small.xplane.pb.gz",
        "instruction_small.programs.json.gz")) < 1 << 18
    (program,) = programs
    assert program["module"] == "jit_mx_train_step" and program["scoped"]
    assert list(program["instructions"]) == list(program["ops"])
    assert len(program["ops"]) == 613
    trace = tr.reduce(path, steps=3)
    assert trace.window_ns == 12707423.0
    busy = trace.chips[0].busy_ns
    assert busy == 409258.0
    it = instruction_time.compute(trace, programs, path)
    assert it.kind_ns == {"matmul_fusion": 170611.0, "wgrad_update": 17715.0,
                          "kernel": 37888.0, "vector": 183044.0}
    assert sum(it.kind_ns.values()) == busy == it.busy_ns
    # one layer's products, three steps: 12 product fusions and 7 weight
    # gradients with Adam behind them (six matrices and the head)
    kinds = [instruction_time.kind(r)
             for r in program["instructions"].values()]
    assert {k: kinds.count(k) for k in instruction_time.KINDS} == {
        "matmul_fusion": 12, "wgrad_update": 7, "kernel": 2, "vector": 592}
    assert it.kind_flops == {"matmul_fusion": 5083496448,
                             "wgrad_update": 2441084928, "kernel": 0,
                             "vector": 0}
    assert sum(it.kind_flops.values()) == 3 * sum(
        r["flops"] for r in program["instructions"].values())
    # forward = weight gradients = the data gradients but the input's:
    # 2 x 16 x 128 x (4 x 128^2 + 2 x 128 x 512 + 128 x 16) a step
    layer = 2 * 16 * 128 * (4 * 128 * 128 + 2 * 128 * 512 + 128 * 16)
    assert it.pass_flops["forward"] == it.kind_flops["wgrad_update"] \
        == 3 * layer
    assert it.pass_ns == {"forward": 225450.0, "recomputed": 55620.0,
                          "backward": 113897.0, "update": 1497.0,
                          "other": 12794.0}
    assert it.pass_flops["recomputed"] == 805306368
    # scope_time's backward is this split's backward + recomputed, and
    # its three other phases are the same to the nanosecond
    st = scope_time.compute(trace, programs, frozenset(), path)
    assert st.phase_ns == {"forward": 225450.0,
                           "backward": 113897.0 + 55620.0,
                           "update": 1497.0, "other": 12794.0}
    # the fused attention kernels: one instance of each in the table (the
    # recomputed segment keeps the forward's output), one call a step
    assert it.kernel_instances == {"mx_attention_train_fwd": 1,
                                   "mx_attention_train_bwd": 1}
    assert it.kernel_calls == {"mx_attention_train_fwd": 3,
                               "mx_attention_train_bwd": 3}
    assert it.missing_ns == 0
    ns, name, executions, record = it.dearest[1]
    assert (ns, name, executions) == (53734.0, "fusion.232", 3)
    assert record["pass"] == "recomputed" and record["flops"] == 268435456
    assert record["scopes"][0].endswith(
        "/ffn/ffn1/checkpoint/rematted_computation/FullyConnected")
    # a weight gradient's fusion holds both names
    wgrad = [r for r in program["instructions"].values()
             if instruction_time.kind(r) == "wgrad_update"]
    assert all(r["pass"] == "backward" and "update" in r["passes"]
               and any(s.endswith("mx.update/adam_update")
                       for s in r["scopes"]) for r in wgrad)
    # both shares are far under 100 at this size (overheads)
    assert 0 < it.roofline_pct("matmul_fusion", PEAK.flops_bf16) < 100
    assert 0 < it.roofline_pct("wgrad_update", PEAK.flops_bf16) < 100


def test_readers_on_the_recorded_pair(monkeypatch, recorded):
    path, programs = recorded
    trace = tr.reduce(path, steps=3)
    got = _through_the_readers(monkeypatch, trace, programs, path)
    assert got["matmul_fusion_device_ms"] == pytest.approx(170611 / 3 / 1e6)
    assert got["wgrad_update_device_ms"] == pytest.approx(17715 / 3 / 1e6)
    assert got["kernel_device_ms"] == pytest.approx(37888 / 3 / 1e6)
    assert got["vector_device_ms"] == pytest.approx(183044 / 3 / 1e6)
    assert got["recompute_device_ms"] == pytest.approx(55620 / 3 / 1e6)
    assert got["kernel_calls_a_step"] == 2.0
    assert got["matmul_fusion_roofline_pct"] == pytest.approx(
        100 * 5083496448 / 170611e-9 / PEAK.flops_bf16)
    assert got["wgrad_update_roofline_pct"] == pytest.approx(
        100 * 2441084928 / 17715e-9 / PEAK.flops_bf16)
    device_step_ms = lookup.metric_reader(
        "layer_metrics", "device_step_ms")({"trace": trace})
    assert got["matmul_fusion_device_ms"] + got["wgrad_update_device_ms"] \
        + got["kernel_device_ms"] + got["vector_device_ms"] \
        == pytest.approx(device_step_ms, rel=1e-12)
    # the same trace under the table as the parent hands it out: nothing
    bare = [{k: v for k, v in programs[0].items() if k != "instructions"}]
    assert _through_the_readers(
        monkeypatch, tr.reduce(path, steps=3), bare, path) \
        == dict.fromkeys(READERS)
