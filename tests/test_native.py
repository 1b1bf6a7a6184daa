"""Native C++ layer tests: dependency engine + recordio.

Model: tests/cpp/engine/threaded_engine_test.cc (randomized dependency-
graph stress asserting serialization order) + dmlc recordio tests
(SURVEY.md §4).  Driven from Python through the ctypes C ABI — the same
binding path users exercise.
"""
import os
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import lib as native
from mxnet_tpu import recordio

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def test_engine_basic_push_and_wait():
    eng = native.NativeEngine(num_workers=4)
    v = eng.new_variable()
    out = []
    eng.push(lambda: out.append(1), write=[v])
    eng.push(lambda: out.append(2), write=[v])
    eng.wait_for_var(v)
    assert out == [1, 2]  # writes on one var are FIFO
    assert eng.var_version(v) == 2
    eng.wait_for_all()
    assert eng.num_pending() == 0


def test_engine_writes_serialize_increments():
    """Unsynchronized += under engine write deps must not lose updates."""
    eng = native.NativeEngine(num_workers=8)
    v = eng.new_variable()
    state = {"x": 0}

    def bump():
        cur = state["x"]
        time.sleep(0.0002)  # widen the race window
        state["x"] = cur + 1

    n = 200
    for _ in range(n):
        eng.push(bump, write=[v])
    eng.wait_for_all()
    assert state["x"] == n


def test_engine_concurrent_reads_exclusive_writes():
    eng = native.NativeEngine(num_workers=8)
    v = eng.new_variable()
    lock = threading.Lock()
    active = {"r": 0, "w": 0, "max_r": 0}
    violations = []

    def reader():
        with lock:
            active["r"] += 1
            active["max_r"] = max(active["max_r"], active["r"])
            if active["w"]:
                violations.append("read during write")
        time.sleep(0.001)
        with lock:
            active["r"] -= 1

    def writer():
        with lock:
            if active["r"] or active["w"]:
                violations.append("write overlap")
            active["w"] += 1
        time.sleep(0.001)
        with lock:
            active["w"] -= 1

    for round_ in range(20):
        for _ in range(6):
            eng.push(reader, read=[v])
        eng.push(writer, write=[v])
    eng.wait_for_all()
    assert not violations
    assert active["max_r"] > 1  # reads actually ran concurrently


def test_engine_random_dag_stress():
    """Randomized read/write sets over many vars; per-var logs must show
    writes in push order with reads fenced between surrounding writes
    (the threaded_engine_test.cc invariant)."""
    rng = np.random.RandomState(0)
    eng = native.NativeEngine(num_workers=8)
    nvars, nops = 8, 300
    vars_ = [eng.new_variable() for _ in range(nvars)]
    logs = [[] for _ in range(nvars)]
    log_lock = threading.Lock()
    # schedule[i] = per-var sequence of ('r'|'w', op_id) in push order
    schedule = [[] for _ in range(nvars)]

    def make_op(op_id, reads, writes):
        def fn():
            with log_lock:
                for r in reads:
                    logs[r].append(("r", op_id))
                for w in writes:
                    logs[w].append(("w", op_id))
        return fn

    for op_id in range(nops):
        k = rng.randint(1, 4)
        chosen = rng.choice(nvars, size=k, replace=False)
        writes = [int(c) for c in chosen[:1]] if rng.rand() < 0.5 else []
        reads = [int(c) for c in chosen[len(writes):]]
        for r in reads:
            schedule[r].append(("r", op_id))
        for w in writes:
            schedule[w].append(("w", op_id))
        eng.push(make_op(op_id, reads, writes),
                 read=[vars_[r] for r in reads],
                 write=[vars_[w] for w in writes])
    eng.wait_for_all()

    for var in range(nvars):
        sched, log = schedule[var], logs[var]
        assert sorted(log) == sorted(sched)
        # writes in push order
        w_sched = [e for e in sched if e[0] == "w"]
        w_log = [e for e in log if e[0] == "w"]
        assert w_log == w_sched, f"var {var}: write order broken"
        # each read runs after its preceding write and before the next one
        prev_write = {}
        next_write = {}
        last_w = None
        for kind, op in sched:
            if kind == "w":
                last_w = op
            else:
                prev_write[op] = last_w
        last_w = None
        for kind, op in reversed(sched):
            if kind == "w":
                last_w = op
            else:
                next_write[op] = last_w
        pos = {e: i for i, e in enumerate(log)}
        for kind, op in sched:
            if kind != "r":
                continue
            if prev_write[op] is not None:
                assert pos[("r", op)] > pos[("w", prev_write[op])], \
                    f"var {var}: read {op} ran before its preceding write"
            if next_write[op] is not None:
                assert pos[("r", op)] < pos[("w", next_write[op])], \
                    f"var {var}: read {op} ran after the next write"


def test_engine_naive_mode_synchronous():
    eng = native.NativeEngine(num_workers=0)
    out = []
    v = eng.new_variable()
    eng.push(lambda: out.append(threading.get_ident()), write=[v])
    # naive engine runs inline on the pushing thread, already done here
    assert out == [threading.get_ident()]
    assert eng.num_pending() == 0


def test_engine_delete_variable():
    eng = native.NativeEngine(num_workers=2)
    v = eng.new_variable()
    out = []
    eng.push(lambda: out.append(1), write=[v])
    eng.delete_variable(v)
    eng.wait_for_all()
    assert out == [1]


def test_engine_cross_var_dependency_chain():
    """a writes v1; b reads v1, writes v2; c reads v2 — strict chain."""
    eng = native.NativeEngine(num_workers=4)
    v1, v2 = eng.new_variable(), eng.new_variable()
    order = []
    eng.push(lambda: (time.sleep(0.005), order.append("a")), write=[v1])
    eng.push(lambda: (time.sleep(0.003), order.append("b")), read=[v1],
             write=[v2])
    eng.push(lambda: order.append("c"), read=[v2])
    eng.wait_for_all()
    assert order == ["a", "b", "c"]


# ---------------------------------------------------------------------------
# recordio interop: python writer <-> native reader and vice versa
# ---------------------------------------------------------------------------

def _payloads(n=20, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.bytes(rng.randint(1, 2000)) for _ in range(n)]


def test_native_reader_reads_python_writer(tmp_path):
    path = str(tmp_path / "py.rec")
    rec = recordio.MXRecordIO(path, "w")
    data = _payloads()
    for p in data:
        rec.write(p)
    rec.close()
    reader = native.NativeRecordReader(path)
    got = []
    while True:
        buf = reader.read()
        if buf is None:
            break
        got.append(buf)
    assert got == data
    reader.reset()
    assert reader.read() == data[0]
    reader.close()


def test_python_reader_reads_native_writer(tmp_path):
    path = str(tmp_path / "native.rec")
    w = native.NativeRecordWriter(path)
    data = _payloads(seed=1)
    positions = [w.write(p) for p in data]
    w.close()
    rec = recordio.MXRecordIO(path, "r")
    got = []
    while True:
        buf = rec.read()
        if buf is None:
            break
        got.append(buf)
    assert got == data
    rec.close()
    # positions support random access via the native reader
    r = native.NativeRecordReader(path)
    r.seek(positions[5])
    assert r.read() == data[5]
    r.close()


def test_native_prefetch_reader(tmp_path):
    path = str(tmp_path / "pf.rec")
    rec = recordio.MXRecordIO(path, "w")
    data = _payloads(n=50, seed=2)
    for p in data:
        rec.write(p)
    rec.close()
    pf = native.NativePrefetchReader(path, capacity=8)
    got = []
    while True:
        buf = pf.read()
        if buf is None:
            break
        got.append(buf)
    assert got == data
    pf.reset()
    got2 = [pf.read() for _ in range(3)]
    assert got2 == data[:3]
    pf.close()


def test_image_record_iter_native_stream(tmp_path):
    """ImageRecordIter streams through the native prefetcher when not
    shuffling."""
    from mxnet_tpu import image as img_mod
    from mxnet_tpu.io import ImageRecordIter

    try:
        img_mod.imencode(np.zeros((8, 8, 3), np.uint8))
    except Exception:
        pytest.skip("no image encoder available")
    path = str(tmp_path / "imgs.rec")
    rec = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    for i in range(7):
        arr = rng.randint(0, 255, size=(10, 10, 3), dtype=np.uint8)
        rec.write(recordio.pack_img(recordio.IRHeader(0, float(i), i, 0),
                                    arr, quality=90))
    rec.close()

    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                         batch_size=3)
    # native path active: the C++ decode pipeline when OpenCV is present,
    # else the C++ prefetch stream
    assert it._pipe is not None or it._stream is not None
    batches = list(it)
    assert len(batches) == 3
    assert batches[-1].pad == 2
    labels = np.concatenate([b.label[0].asnumpy() for b in batches])
    assert labels[:7].tolist() == [0, 1, 2, 3, 4, 5, 6]
    it.reset()
    assert len(list(it)) == 3


def test_runtime_reports_native():
    from mxnet_tpu import runtime

    feats = runtime.Features()
    assert feats.is_enabled("NATIVE_ENGINE")


def test_native_writer_chunked_records(tmp_path):
    """Regression for the 29-bit length mask: the native writer chunk-chains
    oversized records (cflag 1/2/3); both readers rejoin them."""
    from mxnet_tpu import lib, recordio

    path = str(tmp_path / "native_chunked.rec")
    w = lib.NativeRecordWriter(path, max_chunk=32)
    magic = (0x3ED7230A).to_bytes(4, "little")
    payloads = [b"a" * 100, magic * 20, b"b" * 32 * 4, b"tiny"]
    for p in payloads:
        w.write(p)
    w.close()

    nr = lib.NativeRecordReader(path)
    for p in payloads:
        assert nr.read() == p
    assert nr.read() is None
    nr.close()

    pr = recordio.MXRecordIO(path, "r")
    for p in payloads:
        assert pr.read() == p
    assert pr.read() is None
    pr.close()


# ---------------------------------------------------------------------------
# N17: signal handlers + fork safety (mxnet_tpu/initialize.py, lib.py
# fork guards; ref role: src/initialize.cc)
# ---------------------------------------------------------------------------

def test_signal_handler_installed_on_import():
    """faulthandler is armed by package import (MXNET_USE_SIGNAL_HANDLER
    default on) and stays off when explicitly disabled."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import mxnet_tpu, faulthandler;"
            "print(faulthandler.is_enabled())")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "True", r.stdout

    env["MXNET_USE_SIGNAL_HANDLER"] = "0"
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "False", r.stdout


def test_use_after_close_raises_not_crashes(tmp_path):
    """A closed native handle must raise MXNetError, not reach C++ as
    NULL (the old behavior was a hard crash)."""
    from mxnet_tpu import MXNetError

    path = str(tmp_path / "x.rec")
    w = native.NativeRecordWriter(path)
    w.write(b"payload")
    w.close()
    with pytest.raises(MXNetError, match="closed"):
        w.write(b"more")
    r = native.NativeRecordReader(path)
    assert r.read() == b"payload"
    r.close()
    with pytest.raises(MXNetError, match="closed"):
        r.read()


@pytest.mark.skipif(not hasattr(os, "fork"), reason="posix only")
def test_fork_safety_engine_and_reader(tmp_path):
    """Fork with a live engine + reader: the child gets a WORKING engine
    (rebuilt threads) and a loudly-invalid reader; the parent is
    untouched (ref: pthread_atfork engine shutdown, initialize.cc)."""
    from mxnet_tpu import MXNetError

    path = str(tmp_path / "f.rec")
    w = native.NativeRecordWriter(path)
    w.write(b"rec0")
    w.close()

    eng = native.NativeEngine(num_workers=2)
    v = eng.new_variable()
    hits = []
    for _ in range(8):
        eng.push(lambda: hits.append(1), write=[v])
    rd = native.NativeRecordReader(path)

    pid = os.fork()
    if pid == 0:  # child
        rc = 1
        try:
            # engine was rebuilt: usable with fresh worker threads
            cv = eng.new_variable()
            got = []
            eng.push(lambda: got.append(1), write=[cv])
            eng.wait_for_all()
            assert got == [1]
            # reader was invalidated: loud error, no crash
            try:
                rd.read()
            except MXNetError as e:
                assert "fork" in str(e)
                rc = 0
        except BaseException:
            import traceback

            traceback.print_exc()
        os._exit(rc)
    _, status = os.waitpid(pid, 0)
    assert os.waitstatus_to_exitcode(status) == 0
    # parent: pre-fork work all drained by the before-fork quiesce
    assert len(hits) == 8
    eng.wait_for_all()
    assert rd.read() == b"rec0"
    rd.close()


def test_cpp_engine_stress_binary(tmp_path):
    """The C++-native engine test tier (ref: tests/cpp/engine/
    threaded_engine_test.cc): compile src/engine_test.cc and run it —
    FIFO writes, reader/writer exclusion, randomized DAG vs a serial
    oracle, WaitForVar selectivity, all asserted in C++."""
    import shutil
    import subprocess

    if shutil.which("g++") is None:
        pytest.skip("no C++ toolchain")
    src_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    binary = str(tmp_path / "engine_test")
    build = subprocess.run(
        ["g++", "-std=c++17", "-O2", "-pthread",
         os.path.join(src_dir, "engine_test.cc"),
         os.path.join(src_dir, "engine.cc"), "-o", binary],
        capture_output=True, text=True, timeout=240)
    assert build.returncode == 0, build.stderr[-2000:]
    run = subprocess.run([binary], capture_output=True, text=True,
                         timeout=120)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "ALL_OK" in run.stdout


def test_engine_rejects_read_write_overlap():
    """A var in both read and write sets must error loudly, not deadlock
    (ref: threaded_engine.cc duplicate-var CHECK)."""
    from mxnet_tpu import MXNetError

    eng = native.NativeEngine(num_workers=2)
    v = eng.new_variable()
    with pytest.raises(MXNetError, match="BOTH read and write"):
        eng.push(lambda: None, read=[v], write=[v])
    with pytest.raises(MXNetError, match="duplicate variable"):
        eng.push(lambda: None, write=[v, v])
    # engine still healthy afterwards
    done = []
    eng.push(lambda: done.append(1), write=[v])
    eng.wait_for_all()
    assert done == [1]
