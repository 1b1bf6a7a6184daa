"""mxprof (ISSUE 10): always-on step attribution, MFU/HBM accounting,
multi-rank trace merge, and the metric-catalogue contract.

Tier-1 coverage:
  * flight-recorder unit semantics — ring bounds, record closing (the
    `step` span and the self-closing gspmd `spmd-step` boundary),
    phase/byte/compile accumulation, roofline verdicts;
  * MFU math on a known-FLOPs executable (jax cost_analysis -> Cost ->
    mfu = flops / wall / peak), peak-FLOPs resolution order;
  * SIGUSR2 dump end-to-end in this process;
  * multi-rank merge clock-alignment on synthetic 2-rank traces (known
    offset recovered, straggler attributed, merged trace passes
    --check) and the trace_report --json machine format;
  * HBM sampling (allocator stats with the live-array fallback);
  * the registry-scrape contract: train + serve + dataloader exercised
    once — every family the process registered is DECLARED, every
    declared family scrapes;
  * docs-sync: the generated metric table in docs/observability.md
    matches the declarations (gen_metric_docs --write regenerates);
  * the 3% attribution-overhead gate on the fused step path.

Anything spawning worker processes lives in the slow-marked tests at
the bottom (nightly mxprof stage).
"""
import gc
import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd, profiler, telemetry
from mxnet_tpu.gluon import nn, Trainer
from mxnet_tpu.telemetry import catalog, instruments as _ins, mxprof
from mxnet_tpu.telemetry import tracing as _tracing
from mxnet_tpu.telemetry.mxprof import costs, hbm
from mxnet_tpu.telemetry.mxprof.recorder import FlightRecorder

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report_under_mxprof",
        os.path.join(_REPO, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _detached(tmp_path):
    """Every test starts and ends with telemetry off, no profiler
    capture, and no mxprof sink — the overhead gate and the other test
    files depend on the disabled state being truly disabled."""
    telemetry.disable()
    mxprof.disable()  # telemetry.disable() preserves a pre-attached sink
    profiler.stop()
    profiler.dump(finished=True, filename=str(tmp_path / "_flush.json"))
    yield
    telemetry.disable()
    mxprof.disable()
    profiler.stop()
    profiler.dump(finished=True, filename=str(tmp_path / "_flush2.json"))


# ---------------------------------------------------------------------------
# flight recorder unit semantics
# ---------------------------------------------------------------------------

def _close_step(rec, wall=1.0):
    rec.on_event("step", "training", wall, None)


class TestFlightRecorder:
    def test_ring_bounds(self):
        rec = FlightRecorder(ring=8)
        for i in range(20):
            rec.on_event("forward", "training", 0.1, None)
            _close_step(rec)
        recs = rec.records()
        assert len(recs) == 8
        assert [r["step"] for r in recs] == list(range(13, 21))

    def test_phases_accumulate_and_wall_covers_siblings(self):
        rec = FlightRecorder()
        rec.on_event("forward", "training", 0.3, None)
        rec.on_event("backward", "training", 0.5, None)
        rec.on_event("grad-allreduce", "training", 0.05, None)
        rec.on_event("optimizer-update", "training", 0.1, None)
        _close_step(rec, wall=0.2)  # the step span = the update tail
        (r,) = rec.records()
        # forward/backward are siblings of the step span, the record's
        # wall is the whole step
        assert r["wall_s"] == pytest.approx(1.0)
        assert r["phases"]["forward"] == pytest.approx(0.3)
        assert r["verdict"] == "compute-bound"

    def test_spmd_step_self_closing_boundary(self):
        """The gspmd whole-step path has no enclosing `step` span: the
        NEXT spmd-step closes the previous record, whose wall is the
        previous span's duration."""
        rec = FlightRecorder()
        rec.on_event("spmd-step", "training", 0.7, None)
        assert rec.records() == []  # still pending
        rec.on_event("spmd-step", "training", 0.9, None)
        (r,) = rec.records()
        assert r["wall_s"] == pytest.approx(0.7)
        assert r["phases"] == {"spmd-step": pytest.approx(0.7)}

    def test_spmd_flops_after_span_attribute_to_own_step(self):
        """SPMDTrainer reports each step's FLOPs AFTER its spmd-step
        span (parallel/spmd.py): on the self-closing boundary the
        record that closes at the NEXT spmd-step then carries exactly
        one step's FLOPs.  (Reporting before the span shifted flops
        one record early and doubled the first closed record's MFU.)"""
        rec = FlightRecorder()
        for _ in range(3):
            rec.on_event("spmd-step", "training", 0.5, None)
            rec.on_flops("parallel.spmd_step", costs.Cost(1e6, 2e6))
        rec.on_event("spmd-step", "training", 0.5, None)
        assert [r["flops"] for r in rec.records()] == [1e6, 1e6, 1e6]

    def test_verdicts(self):
        rec = FlightRecorder()
        # input-bound: data-wait dominates both halves
        rec.on_event("forward", "training", 0.1, None)
        rec.on_event("data-wait", "data", 5.0, None)
        _close_step(rec)
        # comm-bound: grad-allreduce exceeds compute
        rec.on_event("forward", "training", 0.1, None)
        rec.on_event("grad-allreduce", "training", 2.0, None)
        _close_step(rec)
        # unattributed: a wall but no phases at all
        _close_step(rec, wall=1.0)
        v = [r["verdict"] for r in rec.records()]
        assert v == ["input-bound", "comm-bound", "unattributed"]

    def test_phased_spmd_split_can_reach_comm_bound(self):
        """The phased SPMD capture nests reduce-scatter/shard-update/
        all-gather inside spmd-step; the roofline split must take
        shard-update as the compute half — taking spmd-step would
        swallow the collectives and make comm-bound unreachable
        exactly when the capture exists to split it."""
        rec = FlightRecorder()
        rec.on_event("spmd-step", "training", 9.5, None)
        rec.on_event("reduce-scatter", "training", 1.35, None)
        rec.on_event("shard-update", "training", 3.78, None)
        rec.on_event("all-gather", "training", 3.76, None)
        _close_step(rec, wall=9.5)
        (r,) = rec.records()
        assert r["verdict"] == "comm-bound"  # 5.11 comm > 3.78 compute

    def test_host_collectives_count_as_comm(self):
        rec = FlightRecorder()
        rec.on_event("forward", "training", 0.1, None)
        rec.on_event("allreduce", "collective", 3.0, None)
        _close_step(rec)
        (r,) = rec.records()
        assert r["collectives"] == {"allreduce": pytest.approx(3.0)}
        assert r["verdict"] == "comm-bound"

    def test_bytes_and_compiles(self):
        rec = FlightRecorder()
        rec.on_bytes("all-reduce", "dp", 1000)
        rec.on_bytes("all-reduce", "dp", 24)
        rec.on_bytes("reduce-scatter", "dp", 7)
        rec.on_event("fused-compile", "training", 1.5, None)
        _close_step(rec)
        (r,) = rec.records()
        assert r["collective_bytes"] == {"all-reduce@dp": 1024,
                                         "reduce-scatter@dp": 7}
        assert r["compiles"] == 1
        assert r["compile_s"] == pytest.approx(1.5)
        s = rec.summary()
        assert s["collective_bytes"] == {"all-reduce@dp": 1024,
                                         "reduce-scatter@dp": 7}
        assert s["compiles"] == 1

    def test_empty_step_records_nothing(self):
        rec = FlightRecorder()
        _close_step(rec, wall=0.0)
        assert rec.records() == []

    def test_clear_resets(self):
        rec = FlightRecorder()
        rec.on_event("forward", "training", 0.1, None)
        _close_step(rec)
        rec.on_event("backward", "training", 0.2, None)  # pending
        rec.clear()
        assert rec.records() == []
        _close_step(rec, wall=1.0)
        (r,) = rec.records()
        assert r["step"] == 1 and "backward" not in r["phases"]

    def test_dump_dict_shape(self):
        rec = FlightRecorder()
        rec.on_event("forward", "training", 0.1, None)
        _close_step(rec)
        d = rec.dump_dict(live_hbm=False)
        for key in ("pid", "rank", "uptime_s", "peak_flops", "summary",
                    "hbm", "executable_costs", "records"):
            assert key in d, key
        assert d["summary"]["steps_recorded"] == 1
        json.dumps(d)  # JSON-serializable end to end


# ---------------------------------------------------------------------------
# cost accounting / MFU math
# ---------------------------------------------------------------------------

class _FakeCompiled:
    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca


class TestCosts:
    def test_executable_cost_shapes(self):
        c = costs.executable_cost(_FakeCompiled(
            {"flops": 100.0, "bytes accessed": 40.0}))
        assert c == costs.Cost(100.0, 40.0)
        # jax historically returned a list of one dict
        c = costs.executable_cost(_FakeCompiled([{"flops": 7.0}]))
        assert c.flops == 7.0 and c.bytes_accessed == 0.0
        assert costs.executable_cost(_FakeCompiled(
            NotImplementedError())) is None
        assert costs.executable_cost(_FakeCompiled("nonsense")) is None
        assert costs.executable_cost(_FakeCompiled({})) is None

    def test_peak_flops_resolution(self, monkeypatch):
        monkeypatch.setenv("MXNET_PEAK_FLOPS", "2.5e12")
        assert costs.peak_flops() == (2.5e12, "env")
        monkeypatch.delenv("MXNET_PEAK_FLOPS")
        assert costs.peak_flops("TPU v5e") == (197e12, "table")
        assert costs.peak_flops("TPU v4") == (275e12, "table")
        peak, src = costs.peak_flops("CPU")
        assert peak is None and src == "unknown"

    def test_notes_bounded(self):
        for i in range(costs._NOTES_MAX + 10):
            costs.note("test-site", f"k{i}", costs.Cost(1.0, 1.0))
        assert len(costs.notes()["test-site"]) == costs._NOTES_MAX
        costs.note("test-site", "none", None)  # no-op, never raises

    def test_mfu_math_on_known_flops_executable(self, monkeypatch):
        """The acceptance MFU check: take a REAL executable, read its
        XLA-reported FLOPs, and the recorded step's mfu must be exactly
        flops / wall / peak."""
        import jax
        import jax.numpy as jnp

        compiled = jax.jit(lambda a, b: a @ b).lower(
            jnp.ones((16, 16), jnp.float32),
            jnp.ones((16, 16), jnp.float32)).compile()
        c = costs.executable_cost(compiled)
        assert c is not None and c.flops > 0  # CPU backend reports it
        # matmul flop count is ~2*M*N*K whichever convention XLA uses
        assert 16 ** 3 <= c.flops <= 4 * 16 ** 3

        monkeypatch.setenv("MXNET_PEAK_FLOPS", str(4.0 * c.flops))
        rec = FlightRecorder()
        rec.on_flops("test", c)
        rec.on_event("forward", "training", 1.0, None)
        _close_step(rec, wall=1.0)  # wall = 1.0 + forward 1.0 = 2.0
        (r,) = rec.records()
        assert r["flops"] == pytest.approx(c.flops)
        # mfu = flops / 2.0s / (4*flops/s) = 0.125, exactly
        assert r["mfu"] == pytest.approx(0.125)
        assert rec.summary()["mfu_mean"] == pytest.approx(0.125)

    def test_unknown_peak_reports_none_not_garbage(self, monkeypatch):
        monkeypatch.delenv("MXNET_PEAK_FLOPS", raising=False)
        rec = FlightRecorder()
        rec._peak_cache = (None, "unknown")  # a CPU box
        rec.on_flops("test", costs.Cost(1e9, 0.0))
        _close_step(rec)
        (r,) = rec.records()
        assert r["mfu"] is None

    def test_peak_resolved_before_backend_is_provisional(self,
                                                         monkeypatch):
        """An early dump (SIGUSR2 before any jax work) resolves peak
        while the backend is down — that 'unknown' must NOT be cached
        for the process, or MFU stays null forever on a real TPU."""
        rec = FlightRecorder()
        monkeypatch.setattr(costs, "peak_flops",
                            lambda device_kind=None: (None, "unknown"))
        monkeypatch.setattr(costs, "backend_initialized", lambda: False)
        assert rec._peak() == (None, "unknown")
        assert rec._peak_cache is None  # provisional, not pinned
        monkeypatch.setattr(costs, "peak_flops",
                            lambda device_kind=None: (123.0, "table"))
        monkeypatch.setattr(costs, "backend_initialized", lambda: True)
        assert rec._peak() == (123.0, "table")
        assert rec._peak_cache == (123.0, "table")  # now final

    def test_fused_cache_captures_cost(self):
        """The fused-step compile site stores the executable's cost in
        its cache entry — what on_flops feeds from each step."""
        from mxnet_tpu.optimizer.fused import _FUSED_CACHE

        with _FUSED_CACHE.lock:
            entries = list(_FUSED_CACHE.data.values())
        if not entries:  # no fused step compiled yet in this session
            net = nn.Dense(2, in_units=3)
            net.initialize()
            tr = Trainer(net.collect_params(), "sgd",
                         {"learning_rate": 0.1})
            x = nd.array(np.ones((4, 3), "float32"))
            with autograd.record():
                loss = (net(x) ** 2).sum()
            loss.backward()
            tr.step(4)
            mx.nd.waitall()
            with _FUSED_CACHE.lock:
                entries = list(_FUSED_CACHE.data.values())
        assert entries
        assert any(e.cost is not None and e.cost.flops > 0
                   for e in entries)


# ---------------------------------------------------------------------------
# module surface: enable/disable, SIGUSR2, dumps
# ---------------------------------------------------------------------------

class TestMxprofModule:
    def test_enable_attaches_sink_and_records_steps(self):
        rec = mxprof.enable(ring=32)
        try:
            assert mxprof.enabled()
            assert not telemetry.enabled()  # always-on ≠ telemetry on
            net = nn.Dense(4, in_units=8)
            net.initialize()
            tr = Trainer(net.collect_params(), "sgd",
                         {"learning_rate": 0.1})
            x = nd.array(np.random.rand(8, 8).astype("float32"))
            for _ in range(3):
                with autograd.record():
                    loss = (net(x) ** 2).sum()
                loss.backward()
                tr.step(8)
            mx.nd.waitall()
        finally:
            mxprof.disable()
        assert not mxprof.enabled()
        recs = rec.records()
        assert len(recs) == 3
        for r in recs:
            assert {"forward", "backward"} <= set(r["phases"])
            assert r["wall_s"] > 0
        # the AOT update tail's FLOPs were attributed to some step
        assert sum(r["flops"] for r in recs) > 0

    def test_gspmd_records_carry_equal_per_step_flops(self):
        """End-to-end on the gspmd whole-step path: every closed record
        carries exactly ONE step's whole-program FLOPs.  Regression:
        reporting cost before the spmd-step span put step N+1's FLOPs
        into step N's pending record — the first closed record (the
        one a 2-attribution-step bench commits) read double MFU."""
        from mxnet_tpu import parallel
        from mxnet_tpu.gluon import loss as gloss

        rec = mxprof.enable(ring=16)
        try:
            with parallel.make_mesh(dp=8):
                net = nn.HybridSequential(prefix="mxprof_gspmd_")
                with net.name_scope():
                    net.add(nn.Dense(16, activation="relu"),
                            nn.Dense(8))
                net.initialize(ctx=mx.cpu())
                net(nd.zeros((2, 12)))
                tr = parallel.SPMDTrainer(
                    net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                    {"learning_rate": 0.1})
                rng = np.random.RandomState(3)
                x = rng.randn(16, 12).astype("f4")
                y = (rng.rand(16) * 8).astype(np.int32)
                for _ in range(3):
                    tr.step(x, y)
        finally:
            mxprof.disable()
        recs = [r for r in rec.records()
                if "spmd-step" in r["phases"]]
        assert len(recs) == 2  # 3rd step still pending (self-closing)
        assert recs[0]["flops"] == recs[1]["flops"]
        assert recs[0]["flops"] > 0

    def test_telemetry_bracket_preserves_standalone_recorder(self):
        """An MXNET_MXPROF=1 job brackets telemetry captures all the
        time: telemetry.disable() must restore the sink state it found,
        not silence a recorder the user enabled independently."""
        mxprof.enable()
        try:
            telemetry.enable()
            telemetry.disable()
            assert mxprof.enabled()  # survived the bracket
            # an UNPAIRED defensive disable() must not detach either
            telemetry.disable()
            assert mxprof.enabled()
        finally:
            mxprof.disable()
        # without a pre-attached sink the bracket detaches symmetrically
        telemetry.enable()
        telemetry.disable()
        assert not mxprof.enabled()

    def test_replicated_fused_step_counts_cost_once(self):
        """2 replicas run the SAME fused executable — the step record
        must carry ONE program's FLOPs (per-device MFU), not nrep x."""
        ctxs = [mx.cpu(0), mx.cpu(1)]
        rec1 = {}
        for tag, ctx in (("single", mx.cpu(0)), ("dual", ctxs)):
            rec = mxprof.enable(ring=8)
            try:
                net = nn.Dense(4, in_units=8)
                net.initialize(ctx=ctx)
                tr = Trainer(net.collect_params(), "sgd",
                             {"learning_rate": 0.1})
                x = nd.array(np.random.rand(8, 8).astype("float32"))
                for _ in range(2):
                    with autograd.record():
                        loss = (net(x) ** 2).sum()
                    loss.backward()
                    tr.step(8)
                mx.nd.waitall()
            finally:
                mxprof.disable()
            recs = rec.records()
            assert len(recs) == 2
            rec1[tag] = recs[-1]["flops"]
        assert rec1["single"] > 0
        assert rec1["dual"] == pytest.approx(rec1["single"])

    def test_gauges_update_in_mxprof_only_mode(self):
        """MXNET_MXPROF=1 without MXNET_TELEMETRY: the documented step
        and HBM gauges must still receive values (metric exposition is
        always on; only span EMISSION is behind the telemetry flag)."""
        assert not telemetry.enabled()
        rec = mxprof.enable(ring=8)
        try:
            rec.on_event("forward", "training", 0.25, None)
            rec.on_event("step", "training", 0.05, None)
            assert _ins.step_last_seconds().value == \
                pytest.approx(0.3)
            assert hbm.sample(live=False, state_bytes=512.0)
            assert _ins.hbm_optimizer_state_bytes().value == 512.0
        finally:
            mxprof.disable()

    def test_enable_resize_keeps_state_provider(self):
        """enable(ring=N) swaps in a fresh recorder — the provider the
        Trainer registered must ride along or dumps silently lose the
        optimizer-state share."""
        rec = mxprof.enable(ring=8)
        try:
            mxprof.set_state_bytes_provider(lambda: (1024.0, 4))
            rec2 = mxprof.enable(ring=16)
            assert rec2 is not rec
            assert rec2._state_share() == pytest.approx(256.0)
        finally:
            mxprof.disable()

    def test_telemetry_enable_engages_mxprof(self):
        telemetry.enable()
        try:
            assert mxprof.enabled()
        finally:
            telemetry.disable()
        assert not mxprof.enabled()

    def test_dump_and_snapshot(self, tmp_path):
        mxprof.enable(ring=8)
        try:
            rec = mxprof.recorder()
            rec.on_event("forward", "training", 0.1, None)
            _close_step(rec)
            p = mxprof.dump(str(tmp_path / "prof.json"), live_hbm=False)
            data = json.loads(open(p).read())
            assert data["summary"]["steps_recorded"] == 1
            snap = mxprof.snapshot(live_hbm=False)
            assert snap["records"][0]["phases"]["forward"] == \
                pytest.approx(0.1)
        finally:
            mxprof.disable()
            mxprof.clear()

    def test_default_dump_path_is_rank_qualified(self, monkeypatch):
        """Multi-host regression (ISSUE 13 satellite): containerized
        ranks share pids (every container runs as pid 1), so the
        default dump name must carry jax.process_index() once dist is
        initialized — pid stays the single-process fallback.  The env
        knob still wins over both."""
        from mxnet_tpu.telemetry import tracing as _tr

        prev = _tr._RANK
        try:
            _tr.set_rank(None)
            assert mxprof.default_dump_path() == \
                f"mxprof-{os.getpid()}.json"
            _tr.set_rank(3)  # what dist.init stamps
            assert mxprof.default_dump_path() == "mxprof-rank3.json"
            monkeypatch.setenv("MXNET_MXPROF_DUMP", "explicit.json")
            assert mxprof.default_dump_path() == "explicit.json"
        finally:
            _tr.set_rank(prev)

    def test_default_dump_writes_rank_file(self, tmp_path,
                                           monkeypatch):
        from mxnet_tpu.telemetry import tracing as _tr

        monkeypatch.chdir(tmp_path)
        prev = _tr._RANK
        mxprof.enable(ring=8)
        try:
            _tr.set_rank(7)
            p = mxprof.dump(live_hbm=False)
            assert os.path.basename(p) == "mxprof-rank7.json"
            assert json.loads(open(p).read())["rank"] == 7
        finally:
            _tr.set_rank(prev)
            mxprof.disable()
            mxprof.clear()

    def test_sigusr2_dump(self, tmp_path, monkeypatch):
        dump_path = tmp_path / "sig.json"
        monkeypatch.setenv("MXNET_MXPROF_DUMP", str(dump_path))
        mxprof.enable(ring=8)
        try:
            rec = mxprof.recorder()
            rec.on_event("forward", "training", 0.25, None)
            _close_step(rec)
            assert mxprof.install_sigusr2()
            os.kill(os.getpid(), signal.SIGUSR2)
            deadline = time.time() + 10
            while not dump_path.exists() and time.time() < deadline:
                time.sleep(0.02)
            assert dump_path.exists(), "SIGUSR2 produced no dump"
            data = json.loads(dump_path.read_text())
            assert data["summary"]["steps_recorded"] >= 1
            assert data["pid"] == os.getpid()
        finally:
            mxprof.disable()
            mxprof.clear()

    def test_sigusr2_while_recorder_lock_held(self, tmp_path,
                                              monkeypatch):
        """The signal lands on the main thread, possibly INSIDE the
        recorder lock — the handler must hand the dump to a thread, or
        it deadlocks on the non-reentrant lock it interrupted."""
        dump_path = tmp_path / "locked.json"
        monkeypatch.setenv("MXNET_MXPROF_DUMP", str(dump_path))
        mxprof.enable(ring=8)
        try:
            rec = mxprof.recorder()
            rec.on_event("forward", "training", 0.1, None)
            _close_step(rec)
            assert mxprof.install_sigusr2()
            with rec._lock:  # the window a step-close holds
                os.kill(os.getpid(), signal.SIGUSR2)
                time.sleep(0.2)  # handler ran; dump thread now blocked
                assert not dump_path.exists()
            deadline = time.time() + 10
            while not dump_path.exists() and time.time() < deadline:
                time.sleep(0.02)
            assert dump_path.exists(), "dump thread never completed"
        finally:
            mxprof.disable()
            mxprof.clear()

    def test_state_bytes_provider_via_trainer(self):
        """Trainer._init_kvstore registers the optimizer-state-bytes
        provider; momentum sgd states are one float32 per weight."""
        net = nn.Dense(4, in_units=8)
        net.initialize()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.1, "momentum": 0.9})
        x = nd.array(np.ones((2, 8), "float32"))
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        tr.step(2)
        mx.nd.waitall()
        total, factor = tr.optimizer_state_bytes()
        # momentum state: (8*4 + 4) float32 = 144 bytes, replicated
        assert total == 144 and factor == 1
        snap = mxprof.snapshot(live_hbm=False)
        assert snap["optimizer_state_bytes_per_device"] == \
            pytest.approx(144.0)


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------

class TestHbm:
    def test_sample_with_live_fallback(self):
        keep = nd.array(np.ones((64, 64), "float32"))  # a live buffer
        mx.nd.waitall()
        out = hbm.sample(live=True)
        assert out, "no devices sampled"
        row = next(iter(out.values()))
        assert row["source"] in ("allocator", "live_arrays", "none")
        assert row["peak_bytes"] >= row["used_bytes"] >= 0
        assert hbm.peaks()
        del keep

    def test_memory_summaries_amortized_scan(self):
        import jax

        keep = nd.array(np.ones((128, 128), "float32"))
        mx.nd.waitall()
        per_dev = mx.storage.memory_summaries()
        dev = jax.local_devices()[0]
        n, total = per_dev[dev]
        n1, total1 = mx.storage.live_array_bytes(mx.cpu())
        assert (n, total) == (n1, total1)
        assert total >= 128 * 128 * 4
        del keep


# ---------------------------------------------------------------------------
# multi-rank merge + trace_report --json
# ---------------------------------------------------------------------------

def _x(name, cat, ts, dur, rank=None, pid=7):
    ev = {"name": name, "cat": cat, "ph": "X", "ts": ts, "dur": dur,
          "pid": pid, "tid": 1}
    if rank is not None:
        ev["args"] = {"rank": rank}
    return ev


def _synthetic_rank(rank, clock_off, slow=0.0):
    """3 steps of forward + a blocking collective; `slow` pads this
    rank's forward (the straggler) and `clock_off` shifts its clock."""
    evs = []
    t = 100_000.0 + clock_off
    for _ in range(3):
        evs.append(_x("forward", "training", t, 800 + slow, rank))
        t += 900 + slow
        # the collective END is the sync mark: it completes at the same
        # true time on both ranks, so start/dur absorb the skew
        evs.append(_x("allreduce", "collective", t, 300 - slow, rank))
        t += 400 - slow
    return evs


class TestMerge:
    def test_clock_alignment_recovers_known_offset(self):
        tr = _load_trace_report()
        r0 = _synthetic_rank(0, 0.0)
        r1 = _synthetic_rank(1, 250_000.0, slow=100.0)
        merged, info = tr.merge_traces([(0, r0), (1, r1)])
        # rank1's clock reads +250ms ahead; alignment shifts it back
        assert info["ranks"] == 2
        assert info["aligned_on_marks"]["1"] == 3  # all 3 collectives
        assert info["offsets_us"]["1"] == pytest.approx(-250_000.0,
                                                        abs=300.0)
        assert tr.check_events(merged) == []
        # events re-homed one lane per rank
        assert {ev["pid"] for ev in merged} == {0, 1}
        # straggler attribution: rank1's padded forward is slower
        fwd = [row for row in info["skew"]
               if row["name"] == "forward"][0]
        assert fwd["straggler"] == 1
        assert fwd["skew_ms"] == pytest.approx(0.3, abs=0.01)

    def test_merged_counter_lanes_keyed_per_rank(self):
        """Each rank keeps its OWN cumulative counter lanes: after a
        merge interleaves two ranks' samples, monotonicity must be
        judged per pid — pooled by name, rank interleaving reads as a
        spurious decrease and hard-fails the perf gate."""
        tr = _load_trace_report()

        def lane(pid, ts, v):
            return {"name": "m", "ph": "C", "ts": ts, "pid": pid,
                    "tid": 1, "cat": "c",
                    "args": {"requests_total": v}}

        # rank 0 is ahead of rank 1: pooled ordering would interleave
        # (t=1, 5), (t=2, 3) -> spurious decrease
        merged = [lane(0, 1.0, 5.0), lane(1, 2.0, 3.0),
                  lane(0, 3.0, 6.0), lane(1, 4.0, 4.0)]
        assert tr.check_events(merged) == []
        # a REAL per-rank decrease still fails
        bad = merged + [lane(1, 5.0, 1.0)]
        errs = tr.check_events(bad)
        assert errs and "decreases" in errs[0]

    def test_merge_loaded_shared_pipeline(self, tmp_path):
        """scaling_bench and the CLI --merge branch run the same
        merge_loaded pipeline (rank detect, align, check, write)."""
        tr = _load_trace_report()
        out = str(tmp_path / "m.json")
        merged, info, errs = tr.merge_loaded(
            [_synthetic_rank(0, 0.0), _synthetic_rank(1, 9_000.0)],
            out=out)
        assert errs == [] and info["ranks"] == 2
        assert json.load(open(out))["traceEvents"] == merged

    def test_rank_of_reads_span_tags(self):
        tr = _load_trace_report()
        assert tr._rank_of(_synthetic_rank(3, 0.0), default=9) == 3
        assert tr._rank_of([_x("a", "b", 0, 1)], default=9) == 9

    def test_merge_cli_roundtrip(self, tmp_path):
        tr = _load_trace_report()
        p0, p1 = str(tmp_path / "r0.json"), str(tmp_path / "r1.json")
        json.dump({"traceEvents": _synthetic_rank(0, 0.0)}, open(p0, "w"))
        json.dump({"traceEvents": _synthetic_rank(1, 5_000.0)},
                  open(p1, "w"))
        out = str(tmp_path / "merged.json")
        assert tr.main(["--merge", p0, p1, "--out", out]) == 0
        merged = json.load(open(out))["traceEvents"]
        assert tr.check_events(merged) == []
        # untagged dumps with colliding ranks fall back to file order
        json.dump({"traceEvents": _synthetic_rank(0, 0.0)},
                  open(p1, "w"))
        assert tr.main(["--merge", p0, p1]) == 0

    def test_report_json_machine_format(self, tmp_path):
        tr = _load_trace_report()
        rep = tr.report_json(_synthetic_rank(0, 0.0))
        assert rep["check"]["ok"] and rep["check"]["violations"] == []
        byname = {r["name"]: r for r in rep["phases"]}
        assert byname["forward"]["count"] == 3
        assert byname["forward"]["total_ms"] == pytest.approx(2.4)
        # --json CLI emits the same document
        p = str(tmp_path / "t.json")
        json.dump({"traceEvents": _synthetic_rank(0, 0.0)}, open(p, "w"))
        assert tr.main([p, "--json"]) == 0
        # a broken trace flips the verdict
        bad = _synthetic_rank(0, 0.0)
        del bad[0]["dur"]
        assert not tr.report_json(bad)["check"]["ok"]


# ---------------------------------------------------------------------------
# the metric-catalogue contract: declarations <-> docs <-> scrape
# ---------------------------------------------------------------------------

class TestCatalog:
    def test_docs_in_sync(self):
        """Tier-1 docs-sync gate: a metric added to instruments.py
        without `python tools/gen_metric_docs.py --write` fails here."""
        assert catalog.docs_in_sync(), \
            "docs/observability.md metric table is stale — run " \
            "`python tools/gen_metric_docs.py --write`"

    def test_missing_markers_is_drift(self, tmp_path):
        p = tmp_path / "no_markers.md"
        p.write_text("# docs without the generated block\n")
        with pytest.raises(ValueError):
            catalog.apply_block(str(p))

    def test_write_regenerates(self, tmp_path):
        p = tmp_path / "docs.md"
        p.write_text(f"intro\n\n{catalog.BEGIN_MARK}\nstale\n"
                     f"{catalog.END_MARK}\ntail\n")
        ok, _ = catalog.apply_block(str(p))
        assert not ok
        ok2, new = catalog.apply_block(str(p), write=True)
        assert not ok2 and catalog.docs_in_sync(str(p))
        assert new.startswith("intro") and new.rstrip().endswith("tail")

    def test_drift_checker_sees_spec_declarations(self):
        from mxnet_tpu.analysis import drift

        names = drift.instrument_names(os.path.join(
            _REPO, "mxnet_tpu", "telemetry", "instruments.py"))
        assert {"mx_step_mfu", "mx_hbm_used_bytes",
                "mx_build_info"} <= names


class TestRegistryScrape:
    @pytest.fixture(scope="class")
    def exercised(self, tmp_path_factory):
        """Train + dataloader + serve once with telemetry on, then
        hand back the registry for the coverage assertions."""
        from mxnet_tpu.contrib import deploy
        from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
        from mxnet_tpu import serving

        telemetry.enable()
        try:
            # train (fused path) + dataloader
            net = nn.Dense(4, in_units=8)
            net.initialize()
            tr = Trainer(net.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})
            xs = nd.array(np.random.rand(8, 8).astype("float32"))
            ys = nd.array(np.random.rand(8, 4).astype("float32"))
            loader = DataLoader(ArrayDataset(xs, ys), batch_size=4)
            for x, y in loader:
                with autograd.record():
                    loss = ((net(x) - y) ** 2).sum()
                loss.backward()
                tr.step(4)
            mx.nd.waitall()
            # serve one request
            d = tmp_path_factory.mktemp("mxprof_serve")
            snet = nn.Dense(2, in_units=4)
            snet.initialize()
            deploy.export_model(
                snet, str(d),
                [nd.array(np.ones((4, 4), "float32"))],
                dynamic_batch=True)
            repo = serving.ModelRepository()
            repo.add("m", str(d))
            srv = serving.InferenceServer(
                repo, serving.ServingConfig(max_batch_size=4,
                                            batch_timeout_ms=1.0))
            try:
                srv.submit("m", [nd.array(np.ones((1, 4),
                                          "float32"))]).result(30)
            finally:
                srv.shutdown()
            yield telemetry.get_registry()
        finally:
            telemetry.disable()

    def test_no_undocumented_family_leaks(self, exercised):
        declared = set(_ins.specs())
        live = {fam.name for fam in exercised.families()
                if fam.name.startswith("mx_")}
        assert live <= declared, \
            f"undocumented metric families: {sorted(live - declared)}"

    def test_core_families_actually_recorded(self, exercised):
        live = {fam.name for fam in exercised.families()}
        for must in ("mx_op_dispatch_total", "mx_training_steps_total",
                     "mx_training_phase_seconds", "mx_data_wait_seconds",
                     "mx_fused_step_total", "mx_step_roofline_total",
                     "mx_step_last_seconds",
                     "mx_serving_requests_total",
                     "mx_serving_request_latency_seconds"):
            assert must in live, f"{must} not recorded by the exercise"

    def test_every_declared_family_scrapes(self, exercised):
        """Instantiate every declared family, then the Prometheus text
        must carry a HELP/TYPE header for each — the scrape side of
        the docs contract (incl. build info / uptime / RSS, refreshed
        by the pre-scrape collector)."""
        for name in _ins.specs():
            _ins._family(name)
        text = exercised.to_prometheus()
        for name, spec in _ins.specs().items():
            assert f"# HELP {name} " in text, name
            assert f"# TYPE {name} {spec.kind}" in text, name
        # the process-identity collector populated real values
        assert 'mx_build_info{' in text
        m = [ln for ln in text.splitlines()
             if ln.startswith("mx_process_uptime_seconds")]
        assert m and float(m[0].split()[-1]) > 0
        m = [ln for ln in text.splitlines()
             if ln.startswith("mx_process_rss_bytes")]
        assert m and float(m[0].split()[-1]) > 1e6  # >1MB resident

    def test_build_info_stale_identity_zeroed(self, monkeypatch):
        """When the backend comes up the build-info labels flip
        (uninitialized -> real); the collector must zero the stale
        identity series instead of exporting two conflicting ones."""
        a = _ins._child("mx_build_info",
                        ("v", "j", "uninitialized", "uninitialized"))
        b = _ins._child("mx_build_info", ("v", "j", "cpu", "cpu"))
        monkeypatch.setattr(_ins, "_build_info_last", None)
        monkeypatch.setattr(_ins, "build_info", lambda: a)
        _ins.refresh_process_gauges()
        assert a.value == 1
        monkeypatch.setattr(_ins, "build_info", lambda: b)
        _ins.refresh_process_gauges()
        assert a.value == 0
        assert b.value == 1


# ---------------------------------------------------------------------------
# the 3% attribution-overhead gate (acceptance)
# ---------------------------------------------------------------------------

def test_mxprof_overhead_within_3pct_of_disabled():
    """With the flight recorder attached (no telemetry, no profiler
    capture), a fused training step must cost within 3% of the fully
    disabled path.  A fused step's XLA dispatches jitter by >10% on
    this box, so subtracting two multi-ms timings cannot resolve a 3%
    bound — instead the attribution DELTA is measured directly: the
    exact span/byte/FLOPs feed set one fused step emits, run on the
    real sink path in a tight loop, must cost under 3% of the measured
    disabled step wall.

    Runs with mxtriage imported but idle (no capture armed): triage's
    step-listener hook must keep the budget — its fast path is one
    truthiness check on an empty tuple."""
    from mxnet_tpu.telemetry import mxtriage as _mxtriage
    from mxnet_tpu.telemetry.mxprof import costs as _costs

    assert _mxtriage.active() is None  # triage present but idle

    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=16), nn.Dense(8))
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.1, "momentum": 0.9})
    x = nd.array(np.random.rand(16, 16).astype("float32"))

    def one_step():
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        tr.step(16)
        return loss.asnumpy()  # sync: no async queue buildup

    for _ in range(5):
        one_step()  # warm the executables

    assert not telemetry.enabled() and not profiler.is_running()
    mxprof.disable()

    def best_window(loops, reps, fn):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(loops):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best

    gc.disable()  # a collection inside one window skews the gate
    try:
        # the budget denominator: the disabled step's wall time
        t_step = best_window(20, 5, one_step) / 20

        rec = mxprof.enable(ring=256)
        known = _costs.Cost(1e9, 1e6)

        def per_step_attribution():
            # exactly what a fused step adds when only the sink is on:
            # the sink-only minimal path of every span it emits (the
            # forward scope's two clock reads ride inside span() here),
            # the collective-bytes feed, and the FLOPs feed — including
            # the record close on "step"
            with _tracing.span("forward", cat="training"):
                pass
            with _tracing.span("backward", cat="training"):
                pass
            with _tracing.span("step", cat="training"):
                with _tracing.span("grad-allreduce", cat="training"):
                    pass
                with _tracing.span("optimizer-update", cat="training"):
                    with _tracing.span("fused-update", cat="training"):
                        pass
            rec.on_bytes("all-reduce", "dp", 1 << 20)
            rec.on_flops("optimizer.fused", known)

        t_attr = best_window(2000, 7, per_step_attribution) / 2000
    finally:
        gc.enable()
        mxprof.disable()
        mxprof.clear()
    assert t_attr <= 0.03 * t_step, \
        (f"per-step attribution cost {t_attr * 1e6:.2f}us vs step "
         f"{t_step * 1e6:.1f}us — mxprof overhead "
         f"{t_attr / t_step * 100:.2f}% exceeds the 3% budget")


# ---------------------------------------------------------------------------
# nightly (slow): end-to-end scaling_bench --phases attribution
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_scaling_bench_phases_emits_attribution(tmp_path):
    """One-process `scaling_bench --spmd --phases`: the row must carry
    per-phase seconds, per-step MFU, collective bytes, peak HBM per
    device, and a passing trace-integrity verdict (the 2-process merge
    variant runs in the nightly spmd stage)."""
    out = str(tmp_path / "SCALING_test.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "scaling_bench.py"),
         "--procs", "1", "--model", "mlp", "--spmd", "--phases",
         "--steps", "2", "--warmup", "1", "--no-parity", "--out", out],
        capture_output=True, text=True, timeout=600, cwd=_REPO, env=env)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    rep = json.load(open(out))
    (row,) = rep["sweep"]
    assert row["trace_check_ok"] is True
    assert row["phase_seconds"], "no per-phase attribution"
    # a CPU has no entry in the peak table: the MFU is null, not a
    # utilization against a planted peak
    assert row["mfu"]["peak_flops"] == {"per_device": None,
                                        "source": "unknown"}
    assert row["mfu"]["per_step"] and not any(row["mfu"]["per_step"])
    assert row["hbm_peak_bytes"], "no per-device HBM"
    assert row["collective_bytes"], "no collective bytes"
    assert row["verdicts"]
