"""mxgoodput (ISSUE 14): job-level goodput/badput accounting.

Tier-1 coverage:
  * ledger unit semantics — closure (productive + badput +
    unattributed == wall, nothing silently vanishes), category
    precedence (a data-wait second is never double-counted as
    comm_stall; interval badput inside a step's wall is peeled off
    before the step decomposition), fresh-ledger high-water mark (a
    live recorder's old records are never back-attributed);
  * the attribution hooks — retry backoff (counter independent of the
    ledger, category + per-site when on), checkpoint save/restore
    (blocking-portion-only for async saves), preemption recovery
    known-answer closing at the first post-resume step entry;
  * listener lifecycle across an ``mxprof.enable(ring=N)`` recorder
    swap, and deregistration from the LIVE recorder on disable;
  * the disabled-path zero-overhead gate (mxprof-style);
  * surfaces — the goodput block riding mxprof dumps, the /statusz
    line, the stock goodput_rules alert table, the report tool's
    multi-rank rollup + skew.

The multi-process chaos known-answer e2e (tools/goodput_report.py
strict) is slow-marked at the bottom — the nightly goodput stage runs
it before perf-compare.
"""
import gc
import importlib.util
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd, resilience, telemetry
from mxnet_tpu.gluon import nn, Trainer
from mxnet_tpu.resilience import chaos, preemption
from mxnet_tpu.telemetry import alerts, instruments as _ins
from mxnet_tpu.telemetry import mxgoodput, mxprof
from mxnet_tpu.telemetry import tracing as _tracing
from mxnet_tpu.telemetry.mxgoodput import CATEGORIES, GoodputLedger
from mxnet_tpu.util import env as _env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_goodput_report():
    spec = importlib.util.spec_from_file_location(
        "goodput_report_under_test",
        os.path.join(_REPO, "tools", "goodput_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _detached():
    """Every test starts and ends with goodput off and the mxprof sink
    detached, so cross-test ledgers/listeners never leak."""
    mxgoodput.disable()
    mxprof.disable()
    mxprof.clear()
    preemption.clear()
    yield
    mxgoodput.disable()
    mxprof.disable()
    mxprof.clear()
    preemption.clear()


class _FakeRecorder:
    """records_since/current_step protocol over a fixed record list."""

    def __init__(self, records):
        self._records = list(records)

    def records_since(self, step):
        return [r for r in self._records if r["step"] > step]

    def current_step(self):
        return self._records[-1]["step"] if self._records else 0


def _rec(step, wall=1.0, data_wait=0.0, compile_s=0.0, phases=None,
         collectives=None):
    return {"step": step, "wall_s": wall, "data_wait_s": data_wait,
            "compile_s": compile_s, "phases": phases or {},
            "collectives": collectives or {}}


def _train_tools(units=16, steps=0):
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.Dense(4, in_units=units)
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 1e-3, "momentum": 0.9})
    x = nd.array(np.random.rand(8, units).astype("float32"))

    def one_step():
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        tr.step(8)

    for _ in range(steps):
        one_step()
    return net, tr, one_step


# ---------------------------------------------------------------------------
# ledger unit semantics
# ---------------------------------------------------------------------------

class TestLedgerClosure:
    def test_closure_sums_to_wall(self):
        clock = [100.0]
        led = GoodputLedger(clock=lambda: clock[0])
        clock[0] = 110.0
        led.consume(_FakeRecorder([
            _rec(1, wall=2.0, data_wait=0.5,
                 phases={"grad-allreduce": 0.75}),
            _rec(2, wall=3.0, compile_s=1.0),
        ]))
        led.record_badput("retry_backoff", 0.25, site="s")
        snap = led.snapshot()
        total = (snap["productive_s"] + sum(snap["badput_s"].values())
                 + snap["unattributed_s"])
        assert abs(total - snap["wall_s"]) < 1e-9
        assert snap["closure"]["ok"]
        assert snap["badput_s"]["data_wait"] == pytest.approx(0.5)
        assert snap["badput_s"]["comm_stall"] == pytest.approx(0.75)
        assert snap["badput_s"]["compile"] == pytest.approx(1.0)
        assert snap["badput_s"]["retry_backoff"] == pytest.approx(0.25)
        # productive = (2.0 - 0.75) + (3.0 - 1.0)
        assert snap["productive_s"] == pytest.approx(3.25)
        assert snap["steps"] == 2

    def test_unknown_category_raises(self):
        led = GoodputLedger()
        with pytest.raises(ValueError):
            led.record_badput("coffee_break", 1.0)

    def test_over_attribution_is_exposed_not_hidden(self):
        """Feeds claiming more than the wall: the snapshot clamps
        unattributed at 0 but reports the closure error."""
        clock = [0.0]
        led = GoodputLedger(clock=lambda: clock[0])
        clock[0] = 1.0
        led.record_badput("checkpoint_save", 5.0)
        snap = led.snapshot()
        assert snap["unattributed_s"] == 0.0
        assert snap["closure"]["error_s"] < 0
        assert not snap["closure"]["ok"]

    def test_fresh_ledger_skips_preexisting_records(self):
        """Records a live recorder closed BEFORE the ledger existed
        must not be back-attributed (regression: stage N of a report
        run consumed stage N-1's ring and broke closure)."""
        rec = _FakeRecorder([_rec(1, wall=50.0), _rec(2, wall=50.0)])
        clock = [0.0]
        led = GoodputLedger(clock=lambda: clock[0])
        led.set_record_high_water(rec.current_step())
        clock[0] = 1.0
        assert led.consume(rec) == 0
        snap = led.snapshot()
        assert snap["productive_s"] == 0.0
        assert snap["closure"]["ok"]

    def test_racing_consume_never_folds_twice(self):
        """Two consumes racing on the same new records (listener vs
        snapshot) must fold them once: the under-lock re-filter drops
        records the other consume already took."""
        class _Stale(_FakeRecorder):
            # simulates the racing reader: returns records as if the
            # high-water mark had not advanced yet
            def records_since(self, step):
                return list(self._records)

        clock = [0.0]
        led = GoodputLedger(clock=lambda: clock[0])
        clock[0] = 10.0
        rec = _Stale([_rec(1, wall=2.0)])
        assert led.consume(rec) == 1
        assert led.consume(rec) == 0  # same records offered again
        snap = led.snapshot()
        assert snap["productive_s"] == pytest.approx(2.0)
        assert snap["closure"]["ok"]

    def test_recorder_swap_resets_high_water(self):
        """A clear()ed/swapped recorder restarts step numbering below
        the ledger's mark — consume must notice and not go deaf."""
        clock = [0.0]
        led = GoodputLedger(clock=lambda: clock[0])
        led.set_record_high_water(100)
        clock[0] = 10.0
        n = led.consume(_FakeRecorder([_rec(1, wall=2.0)]))
        assert n == 1
        assert led.snapshot()["productive_s"] == pytest.approx(2.0)


class TestCategoryPrecedence:
    def test_data_wait_never_double_counted_as_comm(self):
        """A step whose collectives nominally exceed its wall: comm is
        capped at the wall, and data-wait (which rides BESIDE the
        wall) is untouched — one second lands in exactly one
        category."""
        clock = [0.0]
        led = GoodputLedger(clock=lambda: clock[0])
        clock[0] = 10.0
        led.consume(_FakeRecorder([
            _rec(1, wall=1.0, data_wait=2.0,
                 collectives={"allreduce": 5.0}),
        ]))
        snap = led.snapshot()
        assert snap["badput_s"]["comm_stall"] == pytest.approx(1.0)
        assert snap["badput_s"]["data_wait"] == pytest.approx(2.0)
        assert snap["productive_s"] == 0.0
        assert snap["closure"]["ok"]

    def test_compile_peeled_before_comm(self):
        clock = [0.0]
        led = GoodputLedger(clock=lambda: clock[0])
        clock[0] = 10.0
        led.consume(_FakeRecorder([
            _rec(1, wall=1.0, compile_s=0.8,
                 collectives={"allreduce": 0.8}),
        ]))
        snap = led.snapshot()
        assert snap["badput_s"]["compile"] == pytest.approx(0.8)
        # only 0.2 of wall left for comm after the compile peel
        assert snap["badput_s"]["comm_stall"] == pytest.approx(0.2)
        assert snap["closure"]["ok"]

    def test_overlapping_interval_peeled_off_step(self):
        """A retry sleep recorded with overlaps_step=True during a
        step is peeled off that step's wall — the seconds keep their
        retry_backoff attribution and are not ALSO productive/comm."""
        clock = [0.0]
        led = GoodputLedger(clock=lambda: clock[0])
        led.record_badput("retry_backoff", 0.4, site="kv",
                          overlaps_step=True)
        clock[0] = 10.0
        led.consume(_FakeRecorder([
            _rec(1, wall=1.0, collectives={"allreduce": 1.0}),
        ]))
        snap = led.snapshot()
        assert snap["badput_s"]["retry_backoff"] == pytest.approx(0.4)
        # the remaining 0.6 of the wall is comm (capped), none doubled
        assert snap["badput_s"]["comm_stall"] == pytest.approx(0.6)
        assert snap["productive_s"] == 0.0
        assert snap["closure"]["ok"]

    def test_between_step_sleep_never_robs_productive(self):
        """Overlap credit from a sleep BETWEEN steps (the next record
        has no comm to peel it from) is discarded, not peeled off
        genuine compute — productive stays whole and the credit does
        not linger to shave a later step either."""
        clock = [0.0]
        led = GoodputLedger(clock=lambda: clock[0])
        led.record_badput("retry_backoff", 0.4, site="between",
                          overlaps_step=True)
        clock[0] = 10.0
        led.consume(_FakeRecorder([_rec(1, wall=1.0)]))  # no comm
        led.consume(_FakeRecorder([
            _rec(1, wall=1.0),
            _rec(2, wall=1.0, collectives={"allreduce": 0.3})]))
        snap = led.snapshot()
        # both steps' compute intact; record 2's comm untouched by the
        # long-gone credit (it was drained at record 1's consume)
        assert snap["productive_s"] == pytest.approx(1.7)
        assert snap["badput_s"]["comm_stall"] == pytest.approx(0.3)
        assert snap["badput_s"]["retry_backoff"] == pytest.approx(0.4)
        assert snap["closure"]["ok"]

    def test_retry_mark_is_thread_scoped(self):
        """A daemon thread's retry sleeps (an async writer retrying a
        flaky filesystem) must not appear in another thread's
        backoff mark — autockpt would deduct them from a concurrent
        sync save's blocking time."""
        import threading

        led = GoodputLedger()

        def daemon_retry():
            led.record_badput("retry_backoff", 0.7, site="ckpt.io",
                              overlaps_step=True)

        t = threading.Thread(target=daemon_retry)
        t.start()
        t.join()
        assert led.category_seconds("retry_backoff") == \
            pytest.approx(0.7)  # global total sees it
        assert led.retry_backoff_this_thread() == 0.0  # this thread's
        led.record_badput("retry_backoff", 0.2, site="here")
        assert led.retry_backoff_this_thread() == pytest.approx(0.2)

    def test_consume_overlap_cancels_credit(self):
        """autockpt deducting retry sleeps from its own measurement
        cancels the step-overlap credit — the next step is not
        shaved."""
        clock = [0.0]
        led = GoodputLedger(clock=lambda: clock[0])
        led.record_badput("retry_backoff", 0.4, site="ckpt",
                          overlaps_step=True)
        led.consume_overlap(0.4)
        clock[0] = 10.0
        led.consume(_FakeRecorder([_rec(1, wall=1.0)]))
        snap = led.snapshot()
        assert snap["productive_s"] == pytest.approx(1.0)
        assert snap["closure"]["ok"]


# ---------------------------------------------------------------------------
# attribution hooks: retry / checkpoint / preemption
# ---------------------------------------------------------------------------

class TestRetryHook:
    def test_backoff_counter_independent_of_goodput(self):
        """mx_retry_backoff_seconds_total grows with goodput DISABLED
        — the sleeps are measured wall-clock either way."""
        assert not mxgoodput.enabled()
        from mxnet_tpu.parallel import dist

        before = _ins.retry_backoff_seconds_total("dist.barrier").value
        with chaos.inject("dist.collective", times=1):
            dist.barrier()
        after = _ins.retry_backoff_seconds_total("dist.barrier").value
        assert after > before

    def test_backoff_lands_in_category_with_site(self):
        from mxnet_tpu.parallel import dist

        mxgoodput.enable(fresh=True)
        with chaos.inject("dist.collective", times=2):
            dist.barrier()
        snap = mxgoodput.snapshot()
        got = snap["badput_s"]["retry_backoff"]
        assert got > 0
        assert snap["retry_backoff_by_site"]["dist.barrier"] == \
            pytest.approx(got)
        assert snap["closure"]["ok"]


class TestCheckpointHook:
    def test_sync_save_and_restore_histograms(self, tmp_path):
        net, tr, one_step = _train_tools(steps=2)
        ck = resilience.AutoCheckpoint(str(tmp_path), tr,
                                       every_n_steps=0,
                                       async_save=False)
        h_save = _ins.ckpt_seconds("save", "sync")
        h_restore = _ins.ckpt_seconds("restore", "sync")
        n0, r0 = h_save.count, h_restore.count
        ck.save(sync=True)
        assert h_save.count == n0 + 1
        ck.resume()
        assert h_restore.count == r0 + 1

    def test_async_save_blocking_portion_only(self, tmp_path,
                                              monkeypatch):
        """A slow daemon write must NOT land in badput (it overlaps
        training); only the snapshot/enqueue half blocks the step
        path.  The daemon time is still recorded, labeled async."""
        net, tr, one_step = _train_tools(steps=2)
        mxgoodput.enable(fresh=True)
        ck = resilience.AutoCheckpoint(str(tmp_path), tr,
                                       every_n_steps=0,
                                       async_save=True)
        orig = resilience.AutoCheckpoint._write_once

        def slow_write(self, snap):
            time.sleep(0.12)
            return orig(self, snap)

        monkeypatch.setattr(resilience.AutoCheckpoint, "_write_once",
                            slow_write)
        h_async = _ins.ckpt_seconds("save", "async")
        a0, s0 = h_async.count, h_async.sum
        ck.save(sync=False)
        ck.flush()
        blocking = mxgoodput.category_seconds("checkpoint_save")
        assert blocking < 0.1, \
            f"daemon write leaked into blocking badput: {blocking}"
        assert h_async.count == a0 + 1
        assert h_async.sum - s0 >= 0.12

    def test_restore_attributed(self, tmp_path):
        net, tr, one_step = _train_tools(steps=2)
        ck = resilience.AutoCheckpoint(str(tmp_path), tr,
                                       every_n_steps=0)
        ck.save(sync=True)
        mxgoodput.enable(fresh=True)
        ck.resume()
        assert mxgoodput.category_seconds("checkpoint_restore") > 0
        assert mxgoodput.snapshot()["closure"]["ok"]


class TestPreemptionRecovery:
    DOWNTIME = 0.15

    def _preempt_resume(self, tmp_path, steps_after=1):
        net, tr, one_step = _train_tools(steps=2)
        mxgoodput.enable(fresh=True)
        ck = resilience.AutoCheckpoint(str(tmp_path), tr,
                                       every_n_steps=0)
        with pytest.raises(preemption.Preempted):
            with chaos.inject("trainer.preempt", at=2):
                for _ in range(4):
                    one_step()
        time.sleep(self.DOWNTIME)
        ck2 = resilience.AutoCheckpoint(str(tmp_path), tr,
                                        every_n_steps=0)
        meta = ck2.resume()
        assert isinstance(meta.get("preempt"), dict)  # stamped save
        for _ in range(steps_after):
            one_step()
        return mxgoodput.snapshot(), tr

    def test_known_answer_downtime(self, tmp_path):
        snap, _tr = self._preempt_resume(tmp_path)
        got = snap["badput_s"]["preemption_recovery"]
        assert self.DOWNTIME - 0.02 <= got <= self.DOWNTIME + 0.5, got
        assert snap["closure"]["ok"]

    def test_preempt_stamp_consumed_on_resume(self, tmp_path):
        """A SECOND resume from the same checkpoint (crash after the
        first resumed run) must not re-open a recovery window back to
        the original SIGTERM — the stamp is consumed by the first
        resume (demoted to preempt_consumed on disk)."""
        snap, tr = self._preempt_resume(tmp_path)
        assert not mxgoodput.ledger().recovery_open()
        ck = resilience.AutoCheckpoint(str(tmp_path), tr,
                                       every_n_steps=0)
        meta = ck.resume()
        assert "preempt" not in meta
        assert "preempt_consumed" in meta  # forensics survive
        assert not mxgoodput.ledger().recovery_open()

    def test_recovery_closes_at_first_step_entry(self, tmp_path):
        net, tr, one_step = _train_tools(steps=2)
        mxgoodput.enable(fresh=True)
        ck = resilience.AutoCheckpoint(str(tmp_path), tr,
                                       every_n_steps=0)
        with pytest.raises(preemption.Preempted):
            with chaos.inject("trainer.preempt", at=1):
                one_step()
        ck2 = resilience.AutoCheckpoint(str(tmp_path), tr,
                                        every_n_steps=0)
        ck2.resume()
        assert mxgoodput.ledger().recovery_open()
        one_step()
        assert not mxgoodput.ledger().recovery_open()
        assert mxgoodput.category_seconds("preemption_recovery") > 0


# ---------------------------------------------------------------------------
# listener lifecycle + enable/disable
# ---------------------------------------------------------------------------

class TestListenerLifecycle:
    def test_listener_survives_ring_swap(self):
        mxgoodput.enable(fresh=True)
        rec = mxprof.enable(ring=64)  # recorder SWAP mid-job
        assert mxgoodput._on_step in rec._listeners
        with _tracing.span("step", cat="training"):
            time.sleep(0.002)
        assert mxgoodput.snapshot()["steps"] == 1

    def test_disable_deregisters_from_live_recorder(self):
        """disable() must remove the listener from the recorder that
        is LIVE NOW — after an enable(ring=N) swap, a removal against
        the stale recorder object would leak the listener."""
        mxgoodput.enable(fresh=True)
        rec = mxprof.enable(ring=32)
        assert mxgoodput._on_step in rec._listeners
        mxgoodput.disable()
        assert mxgoodput._on_step not in mxprof.recorder()._listeners

    def test_fresh_enable_sets_high_water_before_publish(self):
        """enable(fresh=True) on a live recorder: the published ledger
        already carries the recorder's current step as its high-water
        mark (set before publication, so a concurrently-closing step
        can never back-attribute the ring into it)."""
        mxgoodput.enable(fresh=True)
        for _ in range(3):
            with _tracing.span("step", cat="training"):
                pass
        cur = mxprof.recorder().current_step()
        assert cur == 3
        led = mxgoodput.enable(fresh=True)
        assert led._last_step == cur
        assert led.snapshot()["steps"] == 0

    def test_enable_idempotent_one_listener(self):
        mxgoodput.enable(fresh=True)
        mxgoodput.enable()
        mxgoodput.enable()
        n = sum(1 for f in mxprof.recorder()._listeners
                if f is mxgoodput._on_step)
        assert n == 1

    def test_knobs_registered(self):
        for name in ("MXNET_GOODPUT", "MXNET_GOODPUT_MIN",
                     "MXNET_GOODPUT_UNATTRIBUTED_MAX"):
            assert _env.is_declared(name), name


# ---------------------------------------------------------------------------
# the disabled-path zero-overhead gate (mxprof-style)
# ---------------------------------------------------------------------------

def test_goodput_disabled_overhead_within_3pct_of_step(monkeypatch):
    """With mxgoodput imported but DISABLED and only the mxprof sink
    attached, goodput must add literally nothing to the disabled path:
    no listener, and N spans of the per-step attribution feed are N
    `on_event` calls on the sink's minimal path (no Span object, no
    ambient context) and no other work.  What the 3% budget guarded is
    asserted as those counts: a ratio of two wall-clock times on a CPU
    shared by the suite's workers said nothing about this code."""
    net, tr, one_step_train = _train_tools(units=16)
    for _ in range(5):
        one_step_train()

    assert not telemetry.enabled()
    assert not mxgoodput.enabled()
    mxprof.disable()

    feed = ["forward", "backward", "grad-allreduce", "optimizer-update",
            "step"]             # in the order the spans close
    events, notified, yielded = [], [], []
    try:
        mxprof.enable(ring=64)
        assert not mxgoodput.enabled()  # imported, idle
        rec = mxprof.recorder()
        assert mxgoodput._on_step not in rec._listeners
        assert _tracing._SINK is rec

        on_event = rec.on_event
        monkeypatch.setattr(
            rec, "on_event", lambda name, cat, duration, args:
            events.append(name) or on_event(name, cat, duration, args))
        monkeypatch.setattr(mxgoodput, "_on_step", notified.append)

        def no_span(*a, **kw):
            raise AssertionError("the sink-only path built a Span")

        monkeypatch.setattr(_tracing, "Span", no_span)

        def per_step_feed():
            with _tracing.span("forward", cat="training") as s:
                yielded.append(s)
            with _tracing.span("backward", cat="training") as s:
                yielded.append(s)
            with _tracing.span("step", cat="training") as s:
                yielded.append(s)
                with _tracing.span("grad-allreduce", cat="training") as s:
                    yielded.append(_tracing.current_span())
                with _tracing.span("optimizer-update",
                                   cat="training") as s:
                    yielded.append(s)

        loops = 200
        for _ in range(loops):
            per_step_feed()
        steps = len(mxprof.snapshot(live_hbm=False)["records"])
    finally:
        mxprof.disable()
        mxprof.clear()
    assert events == feed * loops
    assert yielded == [None] * (len(feed) * loops)
    assert notified == []
    assert steps == 64                  # the ring, full of closed steps
    assert mxgoodput._on_step not in mxprof.recorder()._listeners


# ---------------------------------------------------------------------------
# surfaces: dump embed, /statusz, alert rules, report rollup
# ---------------------------------------------------------------------------

class TestSurfaces:
    def test_dump_embeds_goodput_block(self):
        mxgoodput.enable(fresh=True)
        with _tracing.span("step", cat="training"):
            time.sleep(0.002)
        snap = mxprof.snapshot(live_hbm=False)
        assert "goodput" in snap
        assert snap["goodput"]["closure"]["ok"]
        assert snap["goodput"]["steps"] == 1

    def test_dump_omits_goodput_when_disabled(self):
        mxprof.enable()
        snap = mxprof.snapshot(live_hbm=False)
        assert "goodput" not in snap

    def test_statusz_renders_goodput_line(self):
        from mxnet_tpu.serving.http import _render_statusz

        class _Stub:
            draining = False

            @staticmethod
            def metrics():
                return {"pending": 0, "max_queue": 8, "models": []}

        page = _render_statusz(_Stub())
        assert "goodput: (mxgoodput not enabled)" in page
        mxgoodput.enable(fresh=True)
        with _tracing.span("step", cat="training"):
            time.sleep(0.002)
        page = _render_statusz(_Stub())
        assert "goodput: 0." in page or "goodput: 1." in page
        assert "unattributed" in page

    def test_goodput_rules_fire_and_resolve(self):
        clock = [0.0]
        eng = alerts.AlertEngine(clock=lambda: clock[0])
        alerts.goodput_rules(eng, min_ratio=0.9, for_s=2.0)
        # absent family: stays inactive, never compares against 0
        assert not eng.tick()
        _ins.goodput_ratio().set(0.4)
        assert not eng.tick()          # pending, inside for-window
        clock[0] = 3.0
        fired = [e for e in eng.tick() if e["state"] == "firing"]
        assert [e["rule"] for e in fired] == ["goodput_below_min"]
        _ins.goodput_ratio().set(0.97)
        resolved = [e for e in eng.tick()
                    if e["state"] == "resolved"]
        assert [e["rule"] for e in resolved] == ["goodput_below_min"]

    def test_preemption_recovery_rule_increase_semantics(self):
        clock = [0.0]
        eng = alerts.AlertEngine(clock=lambda: clock[0])
        alerts.goodput_rules(eng, min_ratio=0.9)
        c = _ins.badput_seconds_total("preemption_recovery")
        eng.tick()                     # baseline the delta
        c.inc(12.5)
        fired = [e for e in eng.tick() if e["state"] == "firing"]
        assert [e["rule"] for e in fired] == ["preemption_recovery"]
        # growth stopped -> the rule RESOLVES (a raw-value rule over a
        # monotone counter would page forever)
        resolved = [e for e in eng.tick()
                    if e["state"] == "resolved"]
        assert [e["rule"] for e in resolved] == ["preemption_recovery"]

    def test_report_merge_rollup_and_skew(self, tmp_path):
        gr = _load_goodput_report()

        def dump(rank, retry_s):
            bad = {c: 0.0 for c in CATEGORIES}
            bad["retry_backoff"] = retry_s
            return {"rank": rank, "goodput": {
                "wall_s": 10.0, "productive_s": 10.0 - retry_s - 1.0,
                "unattributed_s": 1.0, "steps": 5, "badput_s": bad,
                "goodput_ratio": (9.0 - retry_s) / 10.0,
                "closure": {"ok": True, "error_s": 0.0,
                            "accounted_s": 10.0},
            }}

        p0 = tmp_path / "mxprof-rank0.json"
        p1 = tmp_path / "mxprof-rank1.json"
        p0.write_text(json.dumps(dump(0, 0.0)))
        p1.write_text(json.dumps(dump(1, 3.0)))
        merged = gr.merge_dumps([str(p0), str(p1)])
        job = merged["job"]
        assert job["wall_s"] == pytest.approx(20.0)
        assert job["badput_s"]["retry_backoff"] == pytest.approx(3.0)
        assert job["goodput_ratio"] == pytest.approx(
            (9.0 + 6.0) / 20.0)
        skew = merged["badput_skew"]["retry_backoff"]
        assert skew["worst_rank"] == "1"
        assert skew["spread_s"] == pytest.approx(3.0)

    def test_report_merge_rejects_dump_without_goodput(self, tmp_path):
        gr = _load_goodput_report()
        p = tmp_path / "mxprof-rank0.json"
        p.write_text(json.dumps({"rank": 0}))
        with pytest.raises(ValueError):
            gr.merge_dumps([str(p)])

    def test_report_quick_smoke(self, tmp_path):
        """tier-1 smoke: the in-process scenarios run and write the
        artifact (--no-gate; the strict run is the nightly's)."""
        out = tmp_path / "GOODPUT.json"
        p = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools",
                                          "goodput_report.py"),
             "--no-gate", "--quick", "--out", str(out)],
            capture_output=True, text=True, timeout=300, cwd=_REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert p.returncode == 0, p.stdout + p.stderr
        rep = json.loads(out.read_text())
        assert set(rep["stages"]) == {"clean_run", "retry_storm",
                                      "forced_checkpoint",
                                      "preemption"}
        for name, stage in rep["stages"].items():
            assert stage["ok"], (name, stage)


# ---------------------------------------------------------------------------
# nightly (slow): the strict multi-process chaos known-answer e2e
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_goodput_report_e2e_strict():
    """The full chaos known-answer run, STRICT (incl. the 2-process
    rank-dump merge): every injected disruption must land in its own
    category at the injected magnitude, and gate_ok must commit."""
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "GOODPUT.json")
        p = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools",
                                          "goodput_report.py"),
             "--out", out],
            capture_output=True, text=True, timeout=600, cwd=_REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert p.returncode == 0, p.stdout + p.stderr
        with open(out) as f:
            rep = json.load(f)
    assert rep["gate_ok"]
    mr = rep["stages"]["multi_rank_merge"]
    assert mr["ok"] and mr["badput_skew"]["worst_rank"] == "1"
