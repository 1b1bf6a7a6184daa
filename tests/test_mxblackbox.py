"""mxblackbox (ISSUE 17): always-on crash forensics — per-rank event
journals, crash bundles on every abnormal exit, cross-rank incident
reconstruction.

Fast tier-1 lanes: the journal (ring bound, spill/rotation,
torn-line-tolerant reader, signal-safety hand-off — the PR 10 SIGUSR2
self-deadlock regression), the bundle writer (meta-last commit
protocol, index bounds, supervisor scrape with WTERMSIG-resolved exit
records), the postmortem merger (clock alignment on sync marks,
first-failure attribution order, coordinated exits never attributed),
the excepthook chain, the elastic.guard bundle seams, and the
disabled-path 3% overhead gate.  The slow lane is the chaos
known-answer e2e (``tools/postmortem.py --selftest`` runs the same
check as the nightly blackbox stage).
"""
import gc
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.resilience import elastic
from mxnet_tpu.resilience.elastic import (RC_PEER_FAILED, RC_WINDDOWN,
                                          PeerFailed, Supervisor)
from mxnet_tpu.resilience.preemption import Preempted
from mxnet_tpu.telemetry import instruments as _ins, mxblackbox
from mxnet_tpu.telemetry.mxblackbox import (EventJournal, bundle,
                                            postmortem, read_index,
                                            signal_name)
from mxnet_tpu.util import env as _env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counter_value(name, **labels):
    fam = _ins._family(name)
    for values, child in fam.children():
        if dict(zip(fam.labelnames, values)) == labels:
            return child.value
    return 0.0


@pytest.fixture()
def bb(tmp_path, monkeypatch):
    """A fresh, enabled mxblackbox scoped to a tmp dir; module state
    restored afterwards so the rest of the suite sees the default
    (disabled) fast path."""
    from mxnet_tpu.telemetry import tracing

    d = str(tmp_path / "bb")
    monkeypatch.setenv("MXNET_BLACKBOX_DIR", d)
    # a test file that ran earlier in this worker may have left a job
    # rank behind (parallel.dist.init() sets it and nothing unsets it):
    # "fresh" means the journal starts without one
    monkeypatch.setattr(tracing, "_RANK", None)
    saved = (mxblackbox._JOURNAL, mxblackbox._ACTIVE,
             mxblackbox._LAST_BUNDLE)
    mxblackbox._JOURNAL = None
    mxblackbox.enable(hooks=False)
    yield d
    j = mxblackbox._JOURNAL
    if j is not None:
        j.close()
    (mxblackbox._JOURNAL, mxblackbox._ACTIVE,
     mxblackbox._LAST_BUNDLE) = saved


# ---------------------------------------------------------------------------
# the event journal
# ---------------------------------------------------------------------------

class TestJournal:
    def test_ring_bounded_tail_newest_last(self, tmp_path):
        j = EventJournal(directory=None, who="t", ring=32)
        for i in range(100):
            j.emit("alert", f"e{i}", step=i)
        assert len(j) == 32
        t = j.tail(5)
        assert [e["msg"] for e in t] == [f"e{i}" for i in
                                         range(95, 100)]
        assert t[-1]["step"] == 99
        assert t[-1]["rank"] is None
        assert t[-1]["t_unix"] > 0 and t[-1]["t_mono"] > 0

    def test_spill_roundtrip_and_torn_tail_skipped(self, tmp_path):
        j = EventJournal(directory=str(tmp_path), who="r3", rank=3,
                         gen=2)
        for i in range(5):
            j.emit("retry", f"e{i}")
        j.close()
        path = j.spill_path()
        assert path.endswith("journal-r3.jsonl")
        # a hard kill can tear only the LAST line of a single-write
        # append — the reader must skip it and keep everything else
        with open(path, "ab") as f:
            f.write(b'{"category": "torn", "msg"')
        got = EventJournal.read_spill(path)
        assert [e["msg"] for e in got] == [f"e{i}" for i in range(5)]
        assert all(e["rank"] == 3 and e["gen"] == 2 for e in got)
        assert EventJournal.read_spill(path, tail=2)[0]["msg"] == "e3"
        assert EventJournal.read_spill(
            str(tmp_path / "nope.jsonl")) == []

    def test_spill_rotates_once_past_cap(self, tmp_path):
        j = EventJournal(directory=str(tmp_path), who="t",
                         spill_max_bytes=1)  # floors at 64 KiB
        big = "x" * 1024
        for i in range(80):
            j.emit("alert", big, i=i)
        j.close()
        assert os.path.exists(j.spill_path() + ".1")
        # post-rotation entries land in the fresh file
        assert EventJournal.read_spill(j.spill_path())

    def test_unserializable_field_keeps_ring_entry(self, tmp_path):
        j = EventJournal(directory=str(tmp_path), who="t")
        j.emit("health", "obj", detail=threading.Lock())
        j.close()
        assert len(j) == 1
        # repr-serialized rather than dropped
        got = EventJournal.read_spill(j.spill_path())
        assert len(got) == 1 and "lock" in got[0]["detail"]


class TestSignalSafety:
    def test_journal_lock_is_nonreentrant_leaf(self):
        """THE PR 10 regression pin: the journal lock must stay a
        plain (non-reentrant) ``threading.Lock`` — an RLock would let
        an inline signal-handler emit 'work' in the interrupted
        frame and silently reintroduce the self-deadlock class this
        design exists to prevent."""
        j = EventJournal(directory=None, who="t")
        assert type(j._lock) is type(threading.Lock())
        assert j._lock.acquire(blocking=False)
        try:
            # non-reentrant: a second acquire from the SAME thread
            # would block — exactly why the signal path must not
            # take it inline
            assert not j._lock.acquire(blocking=False)
        finally:
            j._lock.release()

    def test_emit_from_signal_while_lock_held_defers_to_drainer(self):
        """A signal that interrupts a frame HOLDING the journal lock
        (i.e. mid-``emit``) must not deadlock: the handler enqueues
        and returns with the lock still held; the daemon drainer
        performs the real emit after release, with the clocks stamped
        at signal time."""
        j = EventJournal(directory=None, who="t")
        fired = []
        old = signal.signal(signal.SIGUSR2,
                            lambda s, f: (j.emit_from_signal(
                                "crash", "from handler", signum=s),
                                fired.append(time.monotonic())))
        try:
            with j._lock:  # the interrupted frame is mid-emit
                t_sig = time.monotonic()
                os.kill(os.getpid(), signal.SIGUSR2)
                deadline = time.monotonic() + 5
                while not fired and time.monotonic() < deadline:
                    time.sleep(0.005)
                # the handler RETURNED while the lock was still held
                assert fired
                # and the real emit has not happened yet (no lock
                # taken inline) — peek lock-free, we hold the lock
                assert len(j._ring) == 0
            deadline = time.monotonic() + 5
            while len(j) == 0 and time.monotonic() < deadline:
                time.sleep(0.005)
            (entry,) = j.tail(1)
            assert entry["msg"] == "from handler"
            assert entry["category"] == "crash"
            # clocks were stamped in the handler, not at drain time
            assert abs(entry["t_mono"] - t_sig) < 1.0
        finally:
            signal.signal(signal.SIGUSR2, old)

    def test_drainer_is_daemon(self):
        j = EventJournal(directory=None, who="t")
        j.emit_from_signal("crash", "x")
        deadline = time.monotonic() + 5
        while len(j) == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(j) == 1
        assert j._drainer.daemon
        assert j._drainer.name == "mx-blackbox-journal"


# ---------------------------------------------------------------------------
# module seams: enable/disable, rank requalification, metrics
# ---------------------------------------------------------------------------

class TestModule:
    def test_disabled_is_noop(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MXNET_BLACKBOX_DIR", str(tmp_path / "n"))
        saved = (mxblackbox._JOURNAL, mxblackbox._ACTIVE)
        mxblackbox._JOURNAL = None
        mxblackbox.disable()
        try:
            assert mxblackbox.emit("alert", "x") is None
            assert mxblackbox.write_crash_bundle("crash") is None
            mxblackbox.emit_from_signal("crash", "x")
            assert mxblackbox._JOURNAL is None  # nothing materialized
            assert not os.path.exists(str(tmp_path / "n"))
        finally:
            mxblackbox._JOURNAL, mxblackbox._ACTIVE = saved

    def test_emit_bumps_category_metric(self, bb):
        before = _counter_value("mx_blackbox_events_total",
                                category="retry")
        entry = mxblackbox.emit("retry", "exhausted", site="s")
        assert entry["site"] == "s"
        assert _counter_value("mx_blackbox_events_total",
                              category="retry") == before + 1

    def test_journal_requalifies_on_rank(self, bb, monkeypatch):
        """mxblackbox auto-enables BEFORE dist.init() knows the rank;
        once the rank lands (tracing.set_rank) the singleton must
        recreate itself rank-qualified — the supervisor scrape looks
        the dead rank's spill up BY rank — carrying the pre-rank
        history into the new ring."""
        from mxnet_tpu.telemetry import tracing

        monkeypatch.setattr(tracing, "_RANK", None)
        mxblackbox.emit("elastic", "pre-rank event")
        j0 = mxblackbox._JOURNAL
        assert j0._who.startswith("p")
        monkeypatch.setattr(tracing, "_RANK", 7)
        mxblackbox.emit("elastic", "post-rank event")
        j1 = mxblackbox._JOURNAL
        assert j1 is not j0 and j1._who == "r7"
        msgs = [e["msg"] for e in j1.tail(10)]
        assert "pre-rank event" in msgs and "post-rank event" in msgs
        assert os.path.exists(os.path.join(bb, "journal-r7.jsonl"))
        # requalification happens ONCE — the next emit reuses it
        mxblackbox.emit("elastic", "again")
        assert mxblackbox._JOURNAL is j1

    def test_knobs_registered(self):
        for name in ("MXNET_BLACKBOX", "MXNET_BLACKBOX_DIR",
                     "MXNET_BLACKBOX_RING", "MXNET_BLACKBOX_SPILL_MB",
                     "MXNET_BLACKBOX_TAIL", "MXNET_BLACKBOX_HISTORY",
                     "MXNET_BLACKBOX_GEN",
                     "MXNET_BLACKBOX_STDERR_TAIL_KB"):
            assert _env.is_declared(name), name


# ---------------------------------------------------------------------------
# crash bundles
# ---------------------------------------------------------------------------

class TestBundle:
    def test_bundle_layout_and_meta_last_commit(self, tmp_path):
        j = EventJournal(directory=str(tmp_path / "b"), who="r0",
                         rank=0)
        for i in range(3):
            j.emit("checkpoint", f"save step {i}", step=i)
        try:
            raise ValueError("boom")
        except ValueError as e:
            d = bundle.write_bundle(
                "crash", reason="uncaught ValueError",
                base_dir=str(tmp_path / "b"), rank=0, step=2, exc=e,
                journal=j, exit_record={"rc": 1})
        j.close()
        assert d is not None and os.path.isdir(d)
        for name in ("meta.json", "journal.json", "mxprof.json",
                     "goodput.json", "alerts.json",
                     "heartbeats.json"):
            assert os.path.exists(os.path.join(d, name)), name
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        assert meta["category"] == "crash" and meta["rank"] == 0
        assert meta["step"] == 2 and meta["exit"] == {"rc": 1}
        assert meta["exception"]["type"] == "ValueError"
        assert "boom" in meta["exception"]["traceback"]
        assert "knob_fingerprint" in meta["config"]
        with open(os.path.join(d, "journal.json")) as f:
            tail = json.load(f)
        assert [e["msg"] for e in tail] == ["save step 0",
                                            "save step 1",
                                            "save step 2"]
        idx = read_index(str(tmp_path / "b"), rank=0)
        assert idx and idx[-1]["dir"] == d
        assert idx[-1]["category"] == "crash"

    def test_index_bounded_and_metaless_dir_skipped(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("MXNET_BLACKBOX_HISTORY", "3")
        base = str(tmp_path / "b")
        for i in range(5):
            bundle.write_bundle("health", reason=f"b{i}",
                                base_dir=base, rank=1, step=i)
        idx = read_index(base, rank=1)
        assert len(idx) == 3
        assert [e["step"] for e in idx] == [2, 3, 4]
        # an interrupted write (no meta.json) is never a bundle
        os.makedirs(os.path.join(base, "crash-99999999-x-r1-9"))
        loaded = postmortem.load_bundles(base)
        assert len(loaded) == 5
        assert all("meta" in b for b in loaded)

    def test_supervisor_scrape_reads_spill_and_stderr(self, tmp_path):
        """The scrape path: the dead rank cannot be asked, but its
        append-only spill survives it — and the exit record keeps the
        SIGNAL so an OOM SIGKILL never reads like a chaos die."""
        base = str(tmp_path / "b")
        j = EventJournal(directory=base, who="r2", rank=2)
        j.emit("elastic", "generation start")
        j.emit("checkpoint", "save step 4", step=4)
        j.close()
        exit_record = {"rc": -9, "signal": 9,
                       "signal_name": "SIGKILL",
                       "supervisor_sigkill": False,
                       "classified": "killed:SIGKILL"}
        d = bundle.write_supervisor_bundle(
            base, 2, exit_record, gen=1,
            stderr_path="gen1-rank2.stderr",
            stderr_tail="Killed\n",
            heartbeat={"age_s": 9.7, "step": 4})
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        assert meta["category"] == "scrape" and meta["rank"] == 2
        assert meta["step"] == 4  # from the last spill entry
        assert meta["exit"]["classified"] == "killed:SIGKILL"
        with open(os.path.join(d, "journal.json")) as f:
            events = json.load(f)
        assert [e["msg"] for e in events] == ["generation start",
                                              "save step 4"]
        with open(os.path.join(d, "stderr.txt")) as f:
            assert f.read() == "Killed\n"
        with open(os.path.join(d, "heartbeats.json")) as f:
            assert json.load(f)["2"]["age_s"] == 9.7

    def test_signal_name(self):
        assert signal_name(9) == "SIGKILL"
        assert signal_name(15) == "SIGTERM"
        assert signal_name(None) is None
        assert signal_name(0) is None


# ---------------------------------------------------------------------------
# last-gasp hooks
# ---------------------------------------------------------------------------

class TestHooks:
    def test_excepthook_writes_bundle_and_chains(self, bb,
                                                 monkeypatch):
        chained = []
        monkeypatch.setattr(mxblackbox, "_PREV_EXCEPTHOOK",
                            lambda *a: chained.append(a))
        try:
            raise ValueError("unhandled boom")
        except ValueError as e:
            mxblackbox._excepthook(ValueError, e, e.__traceback__)
        assert len(chained) == 1  # the previous hook always runs
        d = mxblackbox.last_bundle()
        assert d is not None
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        assert meta["category"] == "crash"
        assert meta["reason"] == "uncaught ValueError"
        assert "unhandled boom" in meta["exception"]["traceback"]

    def test_excepthook_skips_keyboardinterrupt(self, bb,
                                                monkeypatch):
        chained = []
        monkeypatch.setattr(mxblackbox, "_PREV_EXCEPTHOOK",
                            lambda *a: chained.append(a))
        before = mxblackbox.last_bundle()
        mxblackbox._excepthook(KeyboardInterrupt,
                               KeyboardInterrupt(), None)
        assert len(chained) == 1  # chains even when not bundling
        assert mxblackbox.last_bundle() == before


# ---------------------------------------------------------------------------
# elastic integration: guard bundles + supervisor exit records
# ---------------------------------------------------------------------------

class TestElasticSeams:
    def test_guard_peer_failed_writes_bundle(self, bb):
        codes = []
        with elastic.guard(exit_fn=codes.append):
            raise PeerFailed("peer gone", what="allreduce")
        assert codes == [RC_PEER_FAILED]
        d = mxblackbox.last_bundle()
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        assert meta["category"] == "peer_failed"
        assert meta["exit"] == {"rc": RC_PEER_FAILED}
        cats = [e["category"] for e in mxblackbox.recent(10)]
        assert "elastic" in cats  # the observation was journaled too

    def test_guard_preempted_writes_bundle(self, bb):
        codes = []
        with elastic.guard(exit_fn=codes.append):
            raise Preempted("wind-down")
        assert codes == [RC_WINDDOWN]
        d = mxblackbox.last_bundle()
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        assert meta["category"] == "preempted"
        assert meta["exit"] == {"rc": RC_WINDDOWN}

    def test_exit_records_keep_wtermsig(self):
        """The WTERMSIG satellite: a chaos die (rc 1), the
        supervisor's own grace-expiry SIGKILL (hung), an EXTERNAL
        SIGKILL (the OOM killer), and the reserved rcs must all
        classify differently."""

        class P:
            def __init__(self, rc):
                self.returncode = rc

        workers = [{"rank": 0, "proc": P(0)},
                   {"rank": 1, "proc": P(1)},
                   {"rank": 2, "proc": P(-9)},
                   {"rank": 3, "proc": P(-9)},
                   {"rank": 4, "proc": P(RC_PEER_FAILED)},
                   {"rank": 5, "proc": P(RC_WINDDOWN)},
                   {"rank": 6, "proc": P(-11)}]
        recs = Supervisor._exit_records(workers, killed=[3])
        assert recs["0"]["classified"] == "clean"
        assert recs["1"]["classified"] == "died"
        assert recs["1"]["signal"] is None
        assert recs["2"]["classified"] == "killed:SIGKILL"
        assert recs["2"]["signal"] == 9
        assert recs["2"]["supervisor_sigkill"] is False
        assert recs["3"]["classified"] == "hung"
        assert recs["3"]["supervisor_sigkill"] is True
        assert recs["4"]["classified"] == "peer_failed"
        assert recs["5"]["classified"] == "winddown"
        assert recs["6"]["classified"] == "killed:SIGSEGV"
        assert recs["6"]["signal_name"] == "SIGSEGV"


# ---------------------------------------------------------------------------
# postmortem: clock alignment + first-failure attribution
# ---------------------------------------------------------------------------

def _jev(rank, cat, msg, t, step=None, **fields):
    e = {"t_unix": t, "t_mono": t, "rank": rank, "step": step,
         "category": cat, "msg": msg}
    e.update(fields)
    return e


class TestPostmortem:
    def _two_rank_bundles(self, skew=5.0):
        """rank 1's clock runs ``skew`` seconds AHEAD of rank 0's;
        both share the 'generation start' and 'save step 2' sync
        marks.  rank 1 is chaos-killed at true time 103.3 (its clock:
        108.3); rank 0 observes and exits peer_failed at 105.5."""
        r0 = [_jev(0, "elastic", "generation start", 100.0),
              _jev(0, "checkpoint", "save step 2", 102.0, step=2),
              _jev(0, "elastic", "peer failure observed: allreduce",
                   105.5)]
        r1 = [_jev(1, "elastic", "generation start", 100.0 + skew),
              _jev(1, "checkpoint", "save step 2", 102.0 + skew,
                   step=2),
              _jev(1, "chaos",
                   "fault fired at site 'elastic.worker' call #4",
                   103.3 + skew, action="die", nth=4)]
        return [
            {"meta": {"category": "peer_failed", "rank": 0,
                      "t_unix": 105.6, "dir": "/nope",
                      "exit": {"rc": RC_PEER_FAILED}},
             "journal": r0},
            {"meta": {"category": "chaos", "rank": 1, "step": 4,
                      "t_unix": 103.4 + skew, "dir": "/nope",
                      "exit": {"rc": 1}},
             "journal": r1},
        ]

    def test_clock_alignment_on_sync_marks(self):
        rep = postmortem.reconstruct(self._two_rank_bundles(skew=5.0),
                                     epoch=1)
        assert rep["clock"]["offsets_s"]["0"] == 0.0
        assert abs(rep["clock"]["offsets_s"]["1"] + 5.0) < 1e-6
        assert rep["clock"]["aligned_on"]["1"] == 2
        # the merged timeline is causally ordered on ALIGNED time:
        # rank 1's death (true 103.3) precedes rank 0's observation
        # (105.5) despite its raw stamp reading 108.3
        tl = rep["timeline"]
        i_die = next(i for i, e in enumerate(tl)
                     if e["category"] == "chaos")
        i_obs = next(i for i, e in enumerate(tl)
                     if "peer failure" in e["msg"])
        assert i_die < i_obs
        assert abs(tl[i_die]["t_aligned"] - 103.3) < 1e-6

    def test_first_failure_attribution_with_step_backfill(self):
        """The journal chaos fire carries the call count, not the
        step; the same rank's chaos BUNDLE knows the step — the
        attribution must name rank 1 / chaos / step 4, never the
        peer_failed victim."""
        rep = postmortem.reconstruct(self._two_rank_bundles(),
                                     t_detect_unix=104.0, epoch=1)
        ff = rep["first_failure"]
        assert ff["rank"] == 1 and ff["category"] == "chaos"
        assert ff["step"] == 4  # backfilled from the bundle meta
        assert ff["source"] == "journal"
        assert rep["attributed"] is True
        assert abs(rep["detection"]["lag_s"] - 0.7) < 1e-3
        assert rep["incident_id"].startswith("inc-")
        assert "-e1-r1-" in rep["incident_id"]

    def test_coordinated_exits_never_attributed(self):
        """peer_failed/preempted/winddown bundles are victims — with
        no direct evidence the fallback is the exit records, then the
        supervisor's failed list (category 'unknown',
        attributed=False)."""
        b = [{"meta": {"category": "peer_failed", "rank": 0,
                       "t_unix": 10.0, "dir": "/nope"},
              "journal": [_jev(0, "elastic",
                               "peer failure observed: x", 10.0)]}]
        rep = postmortem.reconstruct(
            b, exits={"1": {"rc": -9, "signal": 9,
                            "classified": "killed:SIGKILL"},
                      "0": {"rc": RC_PEER_FAILED, "signal": None}},
            failed_ranks=[1], epoch=2)
        ff = rep["first_failure"]
        assert ff["rank"] == 1 and ff["source"] == "exit"
        assert rep["attributed"] is True
        # nothing at all: supervisor classification only
        rep2 = postmortem.reconstruct([], failed_ranks=[2], epoch=2)
        assert rep2["first_failure"]["category"] == "unknown"
        assert rep2["attributed"] is False

    def test_scrape_bundle_category_from_exit_classification(self):
        b = [{"meta": {"category": "scrape", "rank": 2, "step": 6,
                       "t_unix": 50.0, "dir": "/nope",
                       "exit": {"rc": -9, "signal": 9,
                                "classified": "killed:SIGKILL"}},
              "journal": [_jev(2, "checkpoint", "save step 6", 49.0,
                               step=6)]}]
        rep = postmortem.reconstruct(b, epoch=1)
        ff = rep["first_failure"]
        assert ff["category"] == "killed:SIGKILL"
        assert ff["rank"] == 2 and ff["step"] == 6
        # the failure time is the last journal sign of life, not the
        # scrape's own (detection-side) stamp
        assert abs(ff["t_unix"] - 49.0) < 1e-6

    def test_run_epoch_writes_incident_and_bumps_metric(self,
                                                        tmp_path):
        base = str(tmp_path / "b")
        j = EventJournal(directory=base, who="r1", rank=1)
        # explicit step: an omitted step falls back to the live mxprof
        # counter, which another test's recorder may have advanced
        j.emit("chaos", "fault fired at site 's' call #2",
               step=2, action="die", nth=2)
        d = bundle.write_bundle("chaos", reason="chaos die",
                                base_dir=base, rank=1, step=2,
                                journal=j, exit_record={"rc": 1})
        j.close()
        assert d is not None
        before = _counter_value("mx_incident_total", category="chaos")
        rep = postmortem.run_epoch(base, 1, t_detect_unix=time.time(),
                                   failed_ranks=[1])
        assert rep is not None
        path = os.path.join(base, "INCIDENT-epoch1.json")
        assert rep["path"] == path and os.path.exists(path)
        with open(path) as f:
            disk = json.load(f)
        assert disk["first_failure"]["rank"] == 1
        assert disk["first_failure"]["step"] == 2
        assert _counter_value("mx_incident_total",
                              category="chaos") == before + 1
        # run_epoch is best-effort: a broken input is None, never a
        # raise into the supervisor's recovery path
        assert postmortem.run_epoch(None, 1) is None


# ---------------------------------------------------------------------------
# the disabled-path zero-overhead gate (mxprof-style)
# ---------------------------------------------------------------------------

def test_blackbox_disabled_overhead_within_3pct_of_step():
    """With mxblackbox imported but DISABLED, a training step's worth
    of seam hits (the call shape every feed uses: one ``_ACTIVE``
    check, plus the ``emit()`` early return for seams that call
    through) must cost under 3% of a real step — always-on forensics
    may not tax a job that never crashes."""
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.Dense(4, in_units=16)
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 1e-3, "momentum": 0.9})
    x = nd.array(np.random.rand(8, 16).astype("float32"))

    def one_step():
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        tr.step(8)

    for _ in range(5):
        one_step()

    saved = mxblackbox._ACTIVE
    mxblackbox.disable()

    def best_window(loops, reps, fn):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(loops):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best

    def per_step_seams():
        # ~the densest per-step seam traffic: 8 flag checks (alert,
        # health, chaos, retry, checkpoint x2, compile, elastic) of
        # which 2 call through into emit()'s early return
        for _ in range(6):
            if mxblackbox._ACTIVE:
                raise AssertionError("disabled")
        mxblackbox.emit("health", "x", step=1)
        mxblackbox.emit("checkpoint", "save", step=1)

    gc.disable()
    try:
        t_step = best_window(20, 5, one_step) / 20
        t_attr = best_window(2000, 7, per_step_seams) / 2000
    finally:
        gc.enable()
        mxblackbox._ACTIVE = saved
    assert t_attr <= 0.03 * t_step, \
        (f"per-step seam traffic with mxblackbox imported-but-"
         f"disabled costs {t_attr * 1e6:.2f}us vs step "
         f"{t_step * 1e6:.1f}us — {t_attr / t_step * 100:.2f}% "
         f"exceeds the 3% budget")


# ---------------------------------------------------------------------------
# the chaos known-answer e2e (nightly blackbox stage)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_e2e_chaos_incident_names_rank_category_step(tmp_path):
    """THE ISSUE 17 acceptance: a deterministic chaos kill of rank 1
    at step 4 under the Supervisor yields an INCIDENT.json whose
    first-failure attribution names rank 1 / chaos / step 4, with the
    incident id stamped into the epoch record, the COMMIT marker, and
    (through resume) the goodput recovery window."""
    d = str(tmp_path / "job")
    out = str(tmp_path / "report.json")
    cmd = [sys.executable, os.path.join(_REPO, "tools",
                                        "elastic_run.py"),
           "--workers", "2", "--demo", "--cpu", "--mode", "replace",
           "--steps", "8", "--ckpt-every", "2", "--hb-timeout", "8",
           "--collective-timeout", "6", "--grace", "12", "--dir", d,
           "--out", out, "--chaos", "elastic.worker@4:die:rank=1"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXNET_CHAOS", None)
    env.pop("MXNET_CHAOS_SPEC", None)
    p = subprocess.run(cmd, capture_output=True, text=True,
                       timeout=420, env=env)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    with open(out) as f:
        rep = json.load(f)
    assert rep["ok"] and rep["restarts"] == 1
    epoch = rep["epochs"][0]
    with open(os.path.join(d, "blackbox",
                           "INCIDENT-epoch1.json")) as f:
        inc = json.load(f)
    ff = inc["first_failure"]
    assert ff["rank"] == 1
    assert ff["category"] == "chaos"
    assert ff["step"] == 4
    assert inc["attributed"] is True
    assert inc["detection"]["lag_s"] is not None
    assert sorted(inc["ranks"]) == [0, 1]
    # the chaos die (plain rc 1) classifies as died, NOT as a kill
    assert epoch["exits"]["1"]["classified"] == "died"
    assert epoch["exits"]["1"]["signal"] is None
    # the id flows: epoch record -> COMMIT marker -> resume journal
    assert epoch["incident_id"] == inc["incident_id"]
    commit = elastic.read_commit(d)
    assert commit["incident"] == inc["incident_id"]
    restores = [e for e in EventJournal.read_spill(
        os.path.join(d, "blackbox", "journal-r0.jsonl"))
        if e["msg"].startswith("restore step")]
    assert restores and restores[-1]["incident"] == inc["incident_id"]
    # both failure-side bundles committed: the dying rank's own chaos
    # bundle AND the supervisor's scrape of it
    cats = {b["meta"]["category"]
            for b in postmortem.load_bundles(
                os.path.join(d, "blackbox"))}
    assert {"chaos", "peer_failed", "scrape"} <= cats


@pytest.mark.slow
def test_postmortem_selftest_cli(tmp_path):
    """``tools/postmortem.py --selftest`` (what the nightly blackbox
    stage runs) passes its own gate and writes the artifact."""
    out = str(tmp_path / "INCIDENT.json")
    p = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "postmortem.py"),
         "--selftest", "--out", out],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    with open(out) as f:
        art = json.load(f)
    assert art["gate_ok"] is True
    assert all(art["checks"].values()), art["checks"]
    assert art["first_failure"]["rank"] == 1
