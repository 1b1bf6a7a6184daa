"""scope_time: the join from a trace's instruction names to the program's
scopes, on synthetic events with a hand-made table, and on a small pair
recorded on the chip (one v5e chip, benchmark/tests/record_scope_fixture.py,
PR 24): the trace and the `step_programs()` of the process that made it."""
from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]

from harness import lookup, scope_time  # noqa: E402
from harness import trace_reduce as tr  # noqa: E402

REGISTERED = frozenset({"BatchNorm", "Convolution", "FullyConnected",
                        "Activation", "Pooling", "dot_product_attention",
                        "Dropout", "log_softmax", "pick", "sgd_mom_update",
                        "transpose", "reshape"})
STEP = "jit(mx_train_step)/"
READERS = ("fwd_device_ms", "bwd_device_ms", "optimizer_device_ms",
           "attention_device_ms", "batchnorm_device_ms",
           "scope_unattributed_pct", "host_dispatch_ms")


def test_phase_and_op_scope_of_a_name_stack():
    fwd = STEP + "jvp(net)/stage1/batchnorm0/BatchNorm/reduce_sum"
    bwd = STEP + "transpose(jvp(net))/stage1/batchnorm0/BatchNorm/mul"
    assert scope_time.phase(fwd) == "forward"
    assert scope_time.phase(bwd) == "backward"
    assert scope_time.phase(STEP + "mx.update/sgd_mom_update/sub") == "update"
    assert scope_time.phase(STEP + "jvp(mx.loss)/loss0/pick/mul") == "forward"
    assert scope_time.phase("") == scope_time.phase("params['w']") == "other"
    assert scope_time.op_scope(fwd, REGISTERED) == "BatchNorm"
    assert scope_time.op_scope(bwd, REGISTERED) == "BatchNorm"
    # an op applied outside any block is the wrapped component itself
    assert scope_time.op_scope(STEP + "transpose(jvp(BatchNorm))/mul",
                               REGISTERED) == "BatchNorm"
    # the innermost registered name decides; the last component is the
    # JAX primitive, never a scope, whatever it is called
    assert scope_time.op_scope(
        STEP + "jvp(net)/attn/dot_product_attention/Dropout/mul",
        REGISTERED) == "Dropout"
    assert scope_time.op_scope(
        STEP + "jvp(net)/attn/dot_product_attention/bqk,bkd->bqd/transpose",
        REGISTERED) == "dot_product_attention"
    assert scope_time.op_scope(STEP + "jvp(net)/attn/reshape",
                               REGISTERED) is None
    # a jit of the same name inside the op is not a scope
    assert scope_time.op_scope(
        STEP + "jvp(mx.loss)/loss0/log_softmax/jit(log_softmax)/exp",
        REGISTERED) == "log_softmax"
    # merged instructions: the first path that holds one decides
    assert scope_time.op_scope(
        STEP + "jvp(net)/add;" + STEP + "jvp(net)/bn/BatchNorm/add",
        REGISTERED) == "BatchNorm"
    assert scope_time.instruction(
        "%fusion.14 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop") \
        == "fusion.14"


def test_overlapping_ops_share_the_busy_time_and_do_not_double_it():
    # a while 0-100 with two body ops inside it, then a separate op
    ops = [(0, 100, "w"), (10, 30, "a"), (30, 60, "b"), (120, 130, "c")]
    got = {name: ns for ns, (_s, _e, name) in scope_time.self_times(ops)}
    assert got == {"w": 50, "a": 20, "b": 30, "c": 10}
    assert sum(got.values()) == tr.total(tr.union(
        (s, e) for s, e, _ in ops))


def _ev(s, e, lhs, opcode="fusion"):
    return (s, e, f"%{lhs} = f32[4]{{0}} {opcode}(f32[4]{{0}} %p)")


PROGRAM = {"module": "jit_mx_train_step", "origin": "compiled",
           "scoped": True, "ops": {
               "fusion.1": STEP + "jvp(net)/conv0/Convolution/conv",
               "fusion.2": STEP + "jvp(net)/bn0/BatchNorm/reduce_sum",
               "fusion.3": STEP + "jvp(net)/attn/dot_product_attention/exp",
               "fusion.4": STEP + "transpose(jvp(net))/bn0/BatchNorm/mul",
               "fusion.5": STEP + "transpose(jvp(net))/conv0/Convolution/c",
               "fusion.6": STEP + "mx.update/sgd_mom_update/sub",
               "copy.7": ""}}


def _synthetic(steps=2):
    """One lead-in and `steps` counted steps of 100 ns: forward 0-40
    (conv 0-20, BatchNorm 20-30, attention 30-40), backward 40-70
    (BatchNorm 40-50, conv 50-70), update 70-80, a copy 80-85, and
    between two steps a tiny program whose one instruction shares the
    name `fusion.1`."""
    ops, modules, calls, dispatch = [], [], [], []
    for k in range(steps + 1):
        t = 100 * k
        ops += [_ev(t, t + 20, "fusion.1"), _ev(t + 20, t + 30, "fusion.2"),
                _ev(t + 30, t + 40, "fusion.3"),
                _ev(t + 40, t + 50, "fusion.4"),
                _ev(t + 50, t + 70, "fusion.5"),
                _ev(t + 70, t + 80, "fusion.6"),
                _ev(t + 80, t + 85, "copy.7", "copy"),
                _ev(t + 92, t + 94, "fusion.1")]
        modules += [(t, t + 85, "jit_mx_train_step(1)"),
                    (t + 92, t + 94, "jit__unstack(2)")]
        calls.append((t + 1, t + 9))
        dispatch.append((t + 4, t + 4 + 2 + k))
    trace = tr.from_events({0: ops}, {0: modules},
                           {"bench.step_call": calls}, steps=steps)
    return trace, modules, {"mx.step.dispatch": dispatch,
                            "mx.step": calls}


def test_synthetic_steps_split_by_phase_and_by_op_scope():
    trace, modules, spans = _synthetic()
    assert trace.window == (85, 285) and trace.chips[0].busy_ns == 174
    name, runs = scope_time.step_module(modules)
    assert name == "jit_mx_train_step" and len(runs) == 3
    st = scope_time.attribute(trace, PROGRAM, REGISTERED, step_runs=runs,
                              host_spans=scope_time.counted(spans, trace))
    # the window opens at the end of the lead-in step and closes at the
    # end of the last: two copies and two runs of the foreign program
    assert st.phase_ns == {"forward": 80, "backward": 60, "update": 20,
                           "other": 2 * 5 + 2 * 2}
    assert sum(st.phase_ns.values()) == st.busy_ns == 174
    assert st.op_ns == {"Convolution": 80, "BatchNorm": 40,
                        "dot_product_attention": 20}
    assert st.other_ns == {"copy": 10, "fusion": 4}
    assert st.host_spans["mx.step.dispatch"] == [3, 4]
    assert st.ms_per_step(st.phase_ns["forward"]) == 40e-6
    # without the module runs the foreign `fusion.1` reads as forward
    loose = scope_time.attribute(trace, PROGRAM, REGISTERED)
    assert loose.phase_ns["forward"] == 84


def _run(trace):
    return {"trace": trace, "chips": 1, "samples_per_step": 8}


def _through_the_readers(monkeypatch, trace, programs, path=None):
    monkeypatch.setattr(scope_time, "_from_the_program",
                        lambda: (programs, REGISTERED))
    monkeypatch.setattr(
        tr, "newest_xplane", lambda _dir: path if path is not None else
        (_ for _ in ()).throw(FileNotFoundError(_dir)))
    run = _run(trace)
    return {name: lookup.metric_reader("layer_metrics", name)(run)
            for name in READERS}


def test_readers_on_synthetic_steps(monkeypatch, capsys):
    trace, _modules, _spans = _synthetic()
    got = _through_the_readers(monkeypatch, trace, [PROGRAM])
    # no file: no module runs (the foreign op reads as forward), no spans
    assert got == {
        "fwd_device_ms": 42e-6, "bwd_device_ms": 30e-6,
        "optimizer_device_ms": 10e-6, "attention_device_ms": 10e-6,
        "batchnorm_device_ms": 20e-6,
        "scope_unattributed_pct": pytest.approx(100 * 10 / 174),
        "host_dispatch_ms": None}
    # computed once for the seven of them, and said once
    assert capsys.readouterr().out.count('"scope_time"') == 1


@pytest.mark.parametrize("programs", [
    [], [dict(PROGRAM, scoped=False)],
    # the newest program decides: a stale one after a scoped one
    [PROGRAM, dict(PROGRAM, scoped=False)]])
def test_no_table_or_an_unscoped_program_reads_as_nothing(
        monkeypatch, programs):
    trace, _modules, _spans = _synthetic()
    got = _through_the_readers(monkeypatch, trace, programs)
    assert got == dict.fromkeys(READERS)


def test_no_trace_and_a_program_without_a_table_read_as_nothing(
        monkeypatch):
    for name in READERS:
        assert lookup.metric_reader("layer_metrics", name)(
            _run(None)) is None
    # the parent of PR 24: the import finds no step_programs
    import mxnet_tpu.parallel.spmd as spmd
    monkeypatch.delattr(spmd, "step_programs", raising=False)
    assert scope_time._from_the_program() == ([], ())
    trace, _modules, _spans = _synthetic()
    assert scope_time.read(_run(trace)) is None


def test_every_new_metric_has_its_entry_and_its_cells():
    manifest = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    for name in READERS:
        m = entries[name]
        assert m["source"] == "device_trace" and m["moves"] == "throughput"
        assert m["better"] == "lower"
        want = {"attention_device_ms": [c for c in cells if "bert" in c],
                "batchnorm_device_ms": [c for c in cells if "resnet" in c]
                }.get(name, cells)
        assert sorted(m["workloads"]) == sorted(want)
    # the eight of PR 22 are where they were, the seven after them
    assert [m["name"] for m in manifest["per_layer"]][8:] == list(READERS)


# ---- the recorded pair ------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """benchmark/tests/data/scope_small.*: 2 lead-in + 3 counted steps of
    record_scope_fixture.py's conv + BatchNorm + dense net at batch 64 on
    one v5e chip (PR 24), and the step_programs() of that process."""
    data = os.path.join(HERE, "data")
    path = tmp_path_factory.mktemp("trace") / "scope_small.xplane.pb"
    with gzip.open(os.path.join(data, "scope_small.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with open(os.path.join(data, "scope_small.programs.json")) as f:
        programs = json.load(f)
    return str(path), programs


def test_recorded_pair_adds_up_to_the_busy_time_to_the_nanosecond(recorded):
    path, programs = recorded
    data = os.path.join(HERE, "data")
    assert sum(os.path.getsize(os.path.join(data, f)) for f in (
        "scope_small.xplane.pb.gz", "scope_small.programs.json")) < 1 << 18
    (program,) = programs
    assert program["module"] == "jit_mx_train_step" and program["scoped"]
    trace = tr.reduce(path, steps=3)
    assert trace.window_ns == 11572401.0
    busy = trace.chips[0].busy_ns
    assert busy == 1385226.0
    st = scope_time.compute(trace, programs, REGISTERED, path)
    assert st.program == {"module": "jit_mx_train_step",
                          "origin": "compiled", "instructions": 169}
    assert st.phase_ns == {"forward": 411386.0, "backward": 934457.0,
                           "update": 245.0, "other": 39138.0}
    assert abs(sum(st.phase_ns.values()) - busy) < 1
    # the forward convolution carries the BatchNorm statistics in its
    # epilogue (%convert_reduce_fusion) and the fusion rule books it to
    # the convolution; what is left to BatchNorm is still there
    assert program["ops"]["convert_reduce_fusion"].endswith(
        "/jvp(hybridsequential0)/conv2d0/Convolution/conv_general_dilated")
    assert st.op_ns["Convolution"] == 940746.0
    assert st.op_ns["BatchNorm"] == 265278.0
    # every op of the step is in the table; what no scope placed is the
    # input's layout copy and the three tiny programs between two steps
    assert st.missing_ns == 0
    assert max(st.other_ns, key=st.other_ns.get) == "copy"
    # the program's own spans, inside the three counted calls
    assert {k: len(v) for k, v in st.host_spans.items()} == dict.fromkeys(
        ("mx.step", "mx.step.place", "mx.step.scalars", "mx.step.get_step",
         "mx.step.dispatch", "mx.step.rebind"), 3)
    assert st.host_spans["mx.step.dispatch"] == [648350.0, 565591.0,
                                                 569960.0]
    for k in range(3):
        assert sum(st.host_spans[c][k] for c in st.host_spans
                   if c != "mx.step") <= st.host_spans["mx.step"][k]


def test_readers_on_the_recorded_pair(monkeypatch, recorded):
    path, programs = recorded
    got = _through_the_readers(monkeypatch, tr.reduce(path, steps=3),
                               programs, path)
    assert got["fwd_device_ms"] == pytest.approx(411386.0 / 3 / 1e6)
    assert got["bwd_device_ms"] == pytest.approx(934457.0 / 3 / 1e6)
    assert got["optimizer_device_ms"] == pytest.approx(245.0 / 3 / 1e6)
    assert got["batchnorm_device_ms"] == pytest.approx(265278.0 / 3 / 1e6)
    assert got["attention_device_ms"] == 0.0
    assert got["scope_unattributed_pct"] == pytest.approx(
        100 * 39138.0 / 1385226.0)
    assert got["host_dispatch_ms"] == pytest.approx(0.56996)
    device_step_ms = lookup.metric_reader(
        "layer_metrics", "device_step_ms")(_run(tr.reduce(path, steps=3)))
    assert got["fwd_device_ms"] + got["bwd_device_ms"] \
        + got["optimizer_device_ms"] + device_step_ms \
        * got["scope_unattributed_pct"] / 100 == pytest.approx(device_step_ms)
    # the same trace under a table that is not scoped: nothing
    stale = [dict(programs[0], scoped=False)]
    assert _through_the_readers(
        monkeypatch, tr.reduce(path, steps=3), stale, path) \
        == dict.fromkeys(READERS)
    # and under a table of another module than the trace's step
    other = [dict(programs[0], module="jit_other")]
    assert _through_the_readers(
        monkeypatch, tr.reduce(path, steps=3), other, path) \
        == dict.fromkeys(READERS)
