"""The time to the first step is measured inside the program (PR 36):
`telemetry.tracing.phase` records import, parameter init, cast,
placement, every program's trace / lowering / backend build and the first
call of a new step executable; `ExecutableCache` counts its seconds from
those records; and from the second step on the recorder is never entered.
Counts and identities only: no wall-clock ratio."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.compile_cache import jax_cache
from mxnet_tpu.gluon import Trainer
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.parameter import Parameter
from mxnet_tpu.ndarray.ndarray import array as nd_array
from mxnet_tpu.optimizer import fused
from mxnet_tpu.parallel import spmd
from mxnet_tpu.telemetry import tracing

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUILD = ("mx.build.trace", "mx.build.lower", "mx.build.backend")


def new_records(before):
    return tracing.startup_spans()[before:]


def named(records, name, **stats):
    return [r for r in records if r["name"] == name and all(
        r["stats"].get(k) == v for k, v in stats.items())]


def small_net(units=16):
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(units, in_units=8, activation="relu"),
            nn.Dense(4, in_units=units))
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    net.cast("float32")
    return net


def small_trainer(net, dp=2):
    return parallel.SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=parallel.make_mesh(dp=dp))


def small_batch(n=8):
    rng = np.random.RandomState(0)
    return (rng.rand(n, 8).astype("float32"),
            rng.randint(0, 4, n).astype(np.int32))


@pytest.fixture(scope="module")
def first_step():
    """One net, one trainer, one step: (net, trainer, batch, the records
    the whole of it added, step_compile_stats before and after)."""
    with tracing.phase("t.fence"):  # so that an init an earlier test of
        pass                        # this process left last is not merged into
    before, stats0 = len(tracing.startup_spans()), spmd.step_compile_stats()
    net = small_net()
    trainer = small_trainer(net)
    batch = small_batch()
    trainer.step(*batch).asnumpy()
    return (net, trainer, batch, new_records(before), stats0,
            spmd.step_compile_stats())


# ---- the recorder -------------------------------------------------------

def test_import_span_with_jax_as_its_child_in_a_new_process():
    code = ("import json, sys; assert 'jax' not in sys.modules; "
            "import mxnet_tpu; "
            "from mxnet_tpu.telemetry import tracing; "
            "print(json.dumps([tracing.startup_spans(), "
            "tracing.startup_seconds()]))")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=_REPO, check=True,
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}).stdout
    spans, seconds = json.loads(out.strip().splitlines()[-1])
    imp, jx = spans
    assert (imp["name"], imp["parent"], imp["start"]) == \
        ("mx.setup.import", None, 0.0)
    assert (jx["name"], jx["parent"]) == ("mx.setup.import.jax", imp["id"])
    assert imp["start"] <= jx["start"] <= jx["end"] <= imp["end"]
    assert seconds["mx.setup.import"] == \
        pytest.approx(imp["seconds"] - jx["seconds"], abs=1e-9)
    assert seconds["mx.setup.import.jax"] == jx["seconds"]


def test_import_span_is_the_first_record_here():
    # conftest imports jax first, so this process has no jax child
    first = tracing.startup_spans()[0]
    assert (first["id"], first["name"], first["parent"]) == \
        (0, "mx.setup.import", None)
    assert not named(tracing.startup_spans(), "mx.setup.import.jax")


def test_phase_parents_and_self_seconds_add_up():
    before = len(tracing.startup_spans())
    own0 = tracing.startup_seconds()
    with tracing.phase("t.outer", size=3) as outer:
        with tracing.phase("t.inner") as inner:
            with tracing.phase("t.leaf"):
                pass
        with tracing.phase("t.inner"):
            pass
        outer["stats"]["found"] = "x"
    outer_r, inner_r, leaf_r, inner2_r = new_records(before)
    assert outer_r["parent"] is None
    assert inner_r["parent"] == inner2_r["parent"] == outer_r["id"]
    assert leaf_r["parent"] == inner_r["id"] == inner["id"]
    assert outer_r["stats"] == {"size": 3, "found": "x"}
    for r in (outer_r, inner_r, leaf_r, inner2_r):
        assert r["calls"] == 1
        assert r["seconds"] == pytest.approx(r["end"] - r["start"])
    own = {k: v - own0.get(k, 0.0)
           for k, v in tracing.startup_seconds().items()}
    assert own["t.outer"] == pytest.approx(
        outer_r["seconds"] - inner_r["seconds"] - inner2_r["seconds"])
    assert own["t.inner"] == pytest.approx(
        inner_r["seconds"] + inner2_r["seconds"] - leaf_r["seconds"])
    assert own["t.leaf"] == pytest.approx(leaf_r["seconds"])
    # the self-seconds of a tree add up to its root's duration
    assert own["t.outer"] + own["t.inner"] + own["t.leaf"] == \
        pytest.approx(outer_r["seconds"])


def test_merge_accumulates_into_one_record():
    before = len(tracing.startup_spans())
    for n in (3, 4, 5):
        with tracing.phase("t.merged", merge=True, items=1, elements=n):
            pass
    rec, = new_records(before)
    assert rec["calls"] == 3
    assert rec["stats"] == {"items": 3, "elements": 12}
    assert 0.0 <= rec["seconds"] <= rec["end"] - rec["start"]
    with tracing.phase("t.parent"):         # another parent: its own record
        with tracing.phase("t.merged", merge=True, items=1, elements=1):
            pass
    assert len(named(new_records(before), "t.merged")) == 2
    # and so has an entry that follows another phase under its parent
    with tracing.phase("t.merged", merge=True, items=1, elements=1):
        pass
    assert [r["calls"] for r in named(new_records(before), "t.merged")] \
        == [3, 1, 1]


def test_phase_inside_its_own_name_is_that_phase():
    before = len(tracing.startup_spans())
    with tracing.phase("t.recursive") as outer:
        with tracing.phase("t.recursive") as inner:
            assert inner is outer
    rec, = new_records(before)
    assert rec["calls"] == 1


def test_a_phase_that_raises_is_closed():
    before = len(tracing.startup_spans())
    with pytest.raises(ValueError):
        with tracing.phase("t.raises"):
            raise ValueError("x")
    rec, = new_records(before)
    assert rec["end"] is not None and rec["calls"] == 1
    with tracing.phase("t.after") as after:
        pass
    assert after["parent"] is None


def test_startup_spans_hands_out_copies():
    spans = tracing.startup_spans()
    spans[0]["stats"]["scribble"] = 1
    spans[0]["name"] = "scribble"
    assert tracing.startup_spans()[0]["name"] == "mx.setup.import"
    assert "scribble" not in tracing.startup_spans()[0]["stats"]


# ---- the sites ----------------------------------------------------------

def test_every_site_has_its_record_after_one_step(first_step):
    _net, _trainer, _batch, records, _s0, _s1 = first_step
    init, = named(records, "mx.setup.init")
    assert init["calls"] == init["stats"]["parameters"] == 4
    assert init["stats"]["elements"] == 8 * 16 + 16 + 16 * 4 + 4
    assert len(named(records, "mx.setup.cast")) == 1
    place, = named(records, "mx.setup.place")
    # 4 parameters and one momentum each, float32
    assert place["stats"] == {"arrays": 8, "bytes": 2 * 4 * 212}
    for name in BUILD:
        rec, = named(records, name, program="mx_train_step")
        assert rec["stats"]["site"] == "parallel.spmd_step"
    backend, = named(records, "mx.build.backend")
    assert backend["stats"]["origin"] in ("compiled", "cache")
    first, = named(records, "mx.step.first_dispatch")
    assert first["stats"] == {"site": "parallel.spmd_step"}
    assert all(r["parent"] is None and r["end"] is not None
               for r in records)
    order = [r["name"] for r in records]
    assert order == ["mx.setup.init", "mx.setup.cast", "mx.setup.place",
                     *BUILD, "mx.step.first_dispatch"]
    assert all(a["end"] <= b["start"] + 1e-9 for a, b in
               zip(records[1:], records[2:]))


def test_build_phases_are_the_step_compile_seconds(first_step):
    _net, _trainer, _batch, records, stats0, stats1 = first_step
    assert stats1["count"] + stats1["cache_loads"] \
        - stats0["count"] - stats0["cache_loads"] == 1
    by_stage = {}
    for stage in ("trace", "lower", "backend"):
        rec, = named(records, "mx.build." + stage)
        by_stage[stage] = rec["seconds"]
        assert stats1[stage + "_seconds"] - stats0[stage + "_seconds"] \
            == pytest.approx(rec["seconds"], abs=1e-9)
    # no audit in this process: the three records are the whole of it
    assert not named(records, "mx.build.audit")
    assert stats1["seconds_total"] - stats0["seconds_total"] == \
        pytest.approx(sum(by_stage.values()), abs=1e-9)


def test_first_call_puts_the_bare_executable_in_its_place(first_step):
    _net, trainer, _batch, _records, _s0, _s1 = first_step
    (fn, _cost), = trainer._step_fns.values()
    assert fn in [e.fn for e in spmd._STEP_CACHE.data.values()]
    assert trainer.step_executable() is fn
    assert fn.memory_analysis() is not None


def test_from_step_two_on_the_recorder_is_never_entered(first_step,
                                                        monkeypatch):
    _net, trainer, batch, _records, _s0, stats1 = first_step
    real, reads = time.perf_counter, []
    monkeypatch.setattr(time, "perf_counter",
                        lambda: reads.append(None) or real())
    records, per_step = len(tracing.startup_spans()), []
    for _ in range(4):
        n = len(reads)
        trainer.step(*batch).asnumpy()
        per_step.append(len(reads) - n)
    assert len(tracing.startup_spans()) == records
    assert len(set(per_step)) == 1, per_step
    assert spmd.step_compile_stats() == stats1


def test_the_steady_step_reads_no_clock(first_step, monkeypatch):
    """With telemetry, profiler and sink off, `step()` and what it calls
    in this package read `perf_counter` not once."""
    _net, trainer, batch, _records, _s0, _s1 = first_step
    # the three switches are the process's: a test this worker ran before
    # may have left one on (PR 40's run: an mxprof sink), so they are put
    # off here, and only the reads `step()` makes are counted: those on
    # this thread whose caller is a module of the package (another test's
    # threads read the clock too)
    monkeypatch.setattr(tracing, "_ENABLED", False)
    monkeypatch.setattr(tracing, "_SINK", None)
    assert not tracing.active()
    real, reads, me = time.perf_counter, [], threading.get_ident()

    def counted():
        caller = sys._getframe(1).f_globals.get("__name__", "")
        if threading.get_ident() == me and caller.startswith("mxnet_tpu"):
            reads.append(caller)
        return real()

    monkeypatch.setattr(time, "perf_counter", counted)
    trainer.step(*batch).asnumpy()
    assert reads == []


def test_second_trainer_on_the_block_places_and_builds_nothing(first_step):
    net, trainer, batch, _records, _s0, _s1 = first_step
    trainer.sync_to_block()     # the steps donated what the block held
    before, stats = len(tracing.startup_spans()), spmd.step_compile_stats()
    again = small_trainer(net)
    again.step(*batch).asnumpy()
    assert [r["name"] for r in new_records(before)] == ["mx.setup.place"]
    assert spmd.step_compile_stats() == stats


def test_forward_is_built_ahead_of_time_once_a_shape(first_step):
    net, trainer, batch, _records, _s0, _s1 = first_step
    before, stats = len(tracing.startup_spans()), spmd.step_compile_stats()
    out = trainer.forward(batch[0]).asnumpy()
    built = new_records(before)
    assert [r["name"] for r in built] == list(BUILD)
    assert all(r["stats"]["program"] == "forward"
               and r["stats"]["site"] == "parallel.spmd_forward"
               and r["parent"] is None for r in built)
    assert built[2]["stats"]["origin"] in ("compiled", "cache")
    # not the step's cache: `one_step_program_in_setup` counts that one
    assert spmd.step_compile_stats() == stats
    trainer.sync_to_block()
    with mx.autograd.pause():
        want = net(mx.nd.array(batch[0], ctx=mx.cpu())).asnumpy()
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    # the same shapes again, after a step has replaced the parameters
    trainer.step(*batch).asnumpy()
    trainer.forward(batch[0])
    assert len(new_records(before)) == 3
    trainer.forward(batch[0][:4])           # another shape: another build
    assert [r["name"] for r in new_records(before)[3:]] == list(BUILD)
    assert len(trainer._fwd_fns) == 2


def test_a_new_batch_shape_builds_and_times_its_first_call(first_step):
    _net, trainer, _batch, _records, _s0, _s1 = first_step
    before = len(tracing.startup_spans())
    trainer.step(*small_batch(4)).asnumpy()
    assert [r["name"] for r in new_records(before)] == \
        [*BUILD, "mx.step.first_dispatch"]
    trainer.step(*small_batch(4)).asnumpy()
    assert len(new_records(before)) == 4


def test_the_fused_updaters_build_under_the_same_phases():
    rng = np.random.RandomState(0)
    params = []
    for i, shape in enumerate([(4, 3), (5,)]):
        p = Parameter(f"startup_fused_w{i}", shape=shape)
        p.initialize(ctx=[mx.cpu()])
        p.set_data(nd_array(rng.standard_normal(shape).astype("f4")))
        params.append(p)
    trainer = Trainer(params, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
                      kvstore=None, fuse_step=True)
    before, stats0 = len(tracing.startup_spans()), fused.compile_stats()
    for _ in range(2):
        for p in params:
            p.grad()._data = p.data().data * 0 + 1
        trainer.step(2)
    built = [r for r in new_records(before) if r["name"] in BUILD]
    assert [r["name"] for r in built] == list(BUILD)
    assert {r["stats"]["site"] for r in built} == {"optimizer.fused_step"}
    assert len({r["stats"]["program"] for r in built}) == 1
    stats1 = fused.compile_stats()
    assert stats1["count"] - stats0["count"] == 1
    assert stats1["seconds_total"] - stats0["seconds_total"] == \
        pytest.approx(sum(r["seconds"] for r in built), abs=1e-9)


def test_the_audit_is_a_fourth_phase_only_where_it_is_on(monkeypatch):
    monkeypatch.setenv("MXNET_IR_AUDIT", "1")
    before, stats0 = len(tracing.startup_spans()), spmd.step_compile_stats()
    trainer = small_trainer(small_net(units=24))
    trainer.step(*small_batch()).asnumpy()
    built = [r for r in new_records(before)
             if r["name"].startswith("mx.build.")]
    assert [r["name"] for r in built] == [*BUILD, "mx.build.audit"]
    stats1 = spmd.step_compile_stats()
    assert stats1["audit_seconds"] - stats0["audit_seconds"] == \
        pytest.approx(built[3]["seconds"], abs=1e-9)
    assert stats1["seconds_total"] - stats0["seconds_total"] == \
        pytest.approx(sum(r["seconds"] for r in built), abs=1e-9)
    assert stats1["seconds_total"] == pytest.approx(sum(
        stats1[s + "_seconds"] for s in ("trace", "lower", "backend",
                                         "audit")))


# ---- JAX's own totals ---------------------------------------------------

def test_jax_stage_totals_lie_inside_the_programs_phases():
    """JAX's duration events fire inside `trace()`, `lower()` and
    `compile()`: over one build they rise, the lowering's and the
    backend's by no more than the phase that holds them (the trace's
    counts a jitted function called inside another twice)."""
    import jax
    import jax.numpy as jnp

    def startup_probe(x):
        return jnp.tanh(x) @ x.T

    x = jnp.ones((8, 8))        # a program of its own: before the count
    s0 = jax_cache.seconds()
    assert set(s0) == {"trace", "lower", "backend", "cache_retrieval"}
    build = fused.ProgramBuild(
        lambda: jax.jit(startup_probe).trace(x), "t.probe")
    build.compile()
    s1 = jax_cache.seconds()
    assert build.program == "startup_probe"
    assert s1["trace"] > s0["trace"]
    for stage in ("lower", "backend"):
        assert 0.0 < s1[stage] - s0[stage] <= build.stage_seconds(stage)
    assert s1["cache_retrieval"] - s0["cache_retrieval"] <= \
        build.stage_seconds("backend")
    assert build.seconds == pytest.approx(
        sum(build.stage_seconds(s) for s in ("trace", "lower", "backend")))
    assert build.start == pytest.approx(
        tracing._T0 + build.records[0]["start"])
