"""DataLoader (ref: python/mxnet/gluon/data/dataloader.py).

The reference uses fork()ed worker processes with NDArrays in POSIX shm
(CPUSharedStorage) to parallelise decode/augment.  Forking a process that
holds a PjRt/TPU client is unsafe, so this loader offers two pools:

  * worker_pool="thread" (default): N worker threads + double-buffered
    prefetch.  Full speed when `__getitem__` releases the GIL
    (numpy/cv2/PIL decode); a PURE-python transform serializes on the
    GIL — measured crossover in docs/data.md.
  * worker_pool="process": persistent spawn()-based process pool (spawn,
    not fork, so no PjRt client is inherited; children run CPU-only
    jax).  Escapes the GIL for python-heavy `__getitem__` at the cost of
    one-time worker startup (a jax import per worker).  Batches travel
    through POSIX shared memory by default (worker_transport="shm", the
    reference's CPUSharedStorage role) — the worker writes arrays into
    a segment and ships only the descriptor; "pipe" selects plain
    pickling.

The C++ RecordIO pipeline (src/io, see native/) remains the
high-throughput path for ImageNet-style training.
"""
from __future__ import annotations

import os
import pickle
import queue
import threading
import time
from typing import Callable, Optional

import numpy as np

from ...analysis import sanitizer as _mxsan
from ...base import MXNetError
from ...ndarray.ndarray import NDArray, array as nd_array
from ...resilience import chaos as _chaos
from ...telemetry import instruments as _ins
from ...telemetry import tracing as _tracing
from ...util import env as _env
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "WorkerDied", "default_batchify_fn",
           "default_mp_batchify_fn"]


class WorkerDied(MXNetError):
    """A DataLoader worker (thread or spawned process) exited
    abnormally.  Raised in the CONSUMER with the worker's identity —
    never a silent short epoch, never a hang until the full batch
    timeout.  ``worker`` is the thread name or child pid."""

    def __init__(self, msg: str, worker=None):
        super().__init__(msg)
        self.worker = worker


def _observe_data_wait(t0: float) -> None:
    """Record one consumer-side wait-for-batch: the data-wait gauge +
    histogram (when telemetry is on) and a `data-wait` span in the
    trace (while the profiler captures).  A training step whose
    data-wait dominates is input-bound — the first thing step-time
    attribution must show."""
    dt = time.perf_counter() - t0
    if _tracing._ENABLED:
        _ins.data_wait_seconds().observe(dt)
        _ins.data_wait_last_seconds().set(dt)
    _tracing.record_complete("data-wait", "data", t0, dt)


def _stack_narrow(data):
    """Shared stacking + dtype narrowing (float64->float32,
    int64->int32) used by BOTH batchify variants — one policy."""
    arr = np.asarray(data)
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    if arr.dtype == np.int64:
        arr = arr.astype(np.int32)
    return arr


def _numpy_batchify(data):
    """Child-process batchify: same stacking/dtype rules as
    default_batchify_fn but producing numpy (NDArray construction — and
    with it any jax device touch — stays in the parent)."""
    if isinstance(data[0], tuple):
        return tuple(_numpy_batchify(list(d)) for d in zip(*data))
    if isinstance(data[0], NDArray):
        data = [d.asnumpy() for d in data]
    return _stack_narrow(data)


# ---------------------------------------------------------------------------
# shared-memory batch transport (the CPUSharedStorage role, ref:
# src/storage/cpu_shared_storage_manager.cc): worker processes place the
# assembled batch in a POSIX shm segment and ship only its descriptor;
# the parent maps it with one explicit host copy (jax may alias numpy
# buffers, and the segment is unlinked right after).  vs the pipe this
# removes the serialize+pipe+deserialize copies (measured in
# DATALOADER_BENCH.json / docs/data.md).
# ---------------------------------------------------------------------------

def _shm_pack(out):
    """numpy tree -> (shm_name, spec); spec mirrors the tuple structure
    with ('a', shape, dtype_str, offset) leaves.  A segment is reclaimed
    immediately if packing fails partway — once the tracker registration
    is detached below, an abandoned segment would outlive the process."""
    from multiprocessing import shared_memory

    flat = []

    def walk(x):
        if isinstance(x, tuple):
            return ("t", tuple(walk(e) for e in x))
        a = np.ascontiguousarray(x)
        flat.append(a)
        return ("a", a.shape, a.dtype.str, 0)

    spec = walk(out)
    total = max(sum(a.nbytes for a in flat), 1)
    shm = shared_memory.SharedMemory(create=True, size=total)
    try:
        off = 0
        offs = []
        for a in flat:
            # write in place — tobytes() would add a full transient copy
            np.ndarray(a.shape, a.dtype, buffer=shm.buf,
                       offset=off)[...] = a
            offs.append(off)
            off += a.nbytes
    except Exception:
        try:
            shm.close()
            shm.unlink()
        except Exception:
            pass
        raise

    it = iter(offs)

    def fix(s):
        if s[0] == "t":
            return ("t", tuple(fix(e) for e in s[1]))
        return ("a", s[1], s[2], next(it))

    spec = fix(spec)
    name = shm.name
    # the parent owns the segment's lifetime: detach this process's
    # resource-tracker registration so the child's exit doesn't unlink
    # (nor warn about) a segment the parent is still reading
    try:
        from multiprocessing import resource_tracker

        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:
        pass
    shm.close()
    return name, spec


def _shm_unpack(name, spec):
    """Attach, copy out into NDArrays (the jax device_put is the one
    unavoidable copy), then unlink the segment."""
    from multiprocessing import shared_memory

    shm = shared_memory.SharedMemory(name=name)
    try:
        def walk(s):
            if s[0] == "t":
                return tuple(walk(e) for e in s[1])
            _tag, shape, dt, off = s
            view = np.ndarray(shape, dtype=np.dtype(dt), buffer=shm.buf,
                              offset=off)
            # explicit host copy BEFORE unlink: jax may alias a numpy
            # buffer on the cpu backend, and the mapping dies below
            return nd_array(np.array(view))

        return walk(spec)
    finally:
        shm.close()
        shm.unlink()


def _drain_shm(pending, timeout=120):
    """Reclaim shm segments from unconsumed in-flight pool results.
    `timeout` is per result: callers pass the full loader timeout on a
    healthy teardown (a slow batch still packing must be waited out or
    its segment leaks) and a short cap on the post-error path (a dead
    worker must not stall the exit for timeout x window)."""
    from multiprocessing import shared_memory

    for res in pending:
        try:
            out = res.get(timeout)
        except Exception:
            continue  # failed batches packed nothing
        if isinstance(out, tuple) and len(out) == 3 \
                and out[0] == "__shm__":
            try:
                seg = shared_memory.SharedMemory(name=out[1])
                seg.close()
                seg.unlink()
            except Exception:
                pass


# spawn-child globals (one dataset/batchify per worker process)
_MP_STATE: dict = {}


def _mp_init(dataset_pickle, batchify_fn, transport="shm", chaos_specs=()):
    # Workers are a CPU-only substrate: the parent holds the chip, and a
    # chip belongs to one process.  This runs in EVERY worker — including
    # ones the Pool maintenance thread respawns later with the parent's
    # normal env — so the pinning happens here, not around Pool
    # construction.  jax is already imported by the module bootstrap, but
    # backends attach lazily, and the config update wins over the env.
    # The dataset arrives as bytes and is unpickled only AFTER the pin:
    # a pickled NDArray rebuilds its jax array on the default device, and
    # the pool unpickles initargs before it calls this function.
    import jax

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    _MP_STATE["dataset"] = pickle.loads(dataset_pickle)
    _MP_STATE["batchify"] = batchify_fn
    _MP_STATE["transport"] = transport
    # chaos plans travel into the spawn child so worker-death injection
    # fires INSIDE the worker (each child runs its own call counters)
    _chaos.install_plans(list(chaos_specs))


def _mp_make_batch(indices):
    if _chaos._ACTIVE:
        action = _chaos.check("dataloader.worker")
        if action == "die":
            # simulated abnormal worker death: the parent must raise a
            # clear WorkerDied, not hang or return a short epoch
            os._exit(17)
    ds, bfn = _MP_STATE["dataset"], _MP_STATE["batchify"]
    out = bfn([ds[i] for i in indices])

    def dend(x):  # NDArray from a custom batchify -> plain numpy
        if isinstance(x, NDArray):
            return x.asnumpy()
        if isinstance(x, tuple):
            return tuple(dend(e) for e in x)
        return x

    out = dend(out)
    if _MP_STATE.get("transport") == "shm" and _all_arrays(out):
        try:
            return ("__shm__",) + _shm_pack(out)
        except Exception:
            pass  # fall back to pickling through the pool pipe
    return out


def _all_arrays(x):
    if isinstance(x, tuple):
        return all(_all_arrays(e) for e in x)
    return isinstance(x, np.ndarray)


def default_batchify_fn(data):
    """Stack samples into a batch (ref: dataloader.py::default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        import jax.numpy as jnp

        return NDArray(jnp.stack([d.data for d in data]))
    if isinstance(data[0], tuple):
        return tuple(default_batchify_fn(list(d)) for d in zip(*data))
    return nd_array(_stack_narrow(data))


default_mp_batchify_fn = default_batchify_fn


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, prefetch=None,
                 thread_pool=False, timeout=120, worker_pool=None,
                 worker_transport="shm"):
        self._dataset = dataset
        self._timeout = timeout
        if worker_pool is None:
            worker_pool = "thread"  # docs/data.md: default rationale
        if thread_pool:
            worker_pool = "thread"  # reference-compat flag
        if worker_pool not in ("thread", "process"):
            raise MXNetError("worker_pool must be 'thread' or 'process'")
        if worker_transport not in ("shm", "pipe"):
            raise MXNetError("worker_transport must be 'shm' or 'pipe'")
        self._worker_pool = worker_pool
        self._worker_transport = worker_transport
        self._pool = None  # persistent spawn pool (created lazily)
        if batch_sampler is None:
            if batch_size is None:
                raise MXNetError("batch_size is required when batch_sampler "
                                 "is not given")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle \
                    else SequentialSampler(len(dataset))
            elif shuffle:
                raise MXNetError("shuffle must be False with explicit sampler")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif (batch_size is not None or shuffle or sampler is not None
              or last_batch is not None):
            raise MXNetError("batch_size/shuffle/sampler/last_batch must not "
                             "be set with explicit batch_sampler")
        self._batch_sampler = batch_sampler
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._num_workers = max(0, num_workers)
        if prefetch is None:
            # tunable knob (mxtune sweep dimension); the explicit
            # prefetch= argument always wins, and the declared default
            # is dynamic: 2 * num_workers
            prefetch = _env.get_int("MXNET_PREFETCH_DEPTH")
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        self._resume_from = 0

    def resume_from(self, batch_idx: int) -> None:
        """Preemption-resume contract: the NEXT ``__iter__`` starts at
        `batch_idx` (0-based), skipping earlier batches without
        building them.  One-shot — the following epoch starts at 0.
        Determinism is the sampler's: with ``shuffle=True`` the caller
        must restore the RNG first (resilience.AutoCheckpoint does)."""
        self._resume_from = max(0, int(batch_idx))

    def _make_batch(self, indices):
        return self._batchify_fn([self._dataset[i] for i in indices])

    def __iter__(self):
        start, self._resume_from = self._resume_from, 0
        if self._num_workers == 0:
            for bi, indices in enumerate(self._batch_sampler):
                if bi < start:
                    continue
                if not _tracing.active():
                    yield self._make_batch(indices)
                    continue
                t0 = time.perf_counter()
                batch = self._make_batch(indices)
                _observe_data_wait(t0)
                yield batch
            return
        if self._worker_pool == "process":
            yield from self._process_iter(start)
        else:
            yield from self._threaded_iter(start)

    # ---- spawn-based process pool ---------------------------------------
    def _get_pool(self):
        if self._pool is None:
            import multiprocessing as mp

            ctx = mp.get_context("spawn")
            bfn = self._batchify_fn
            if bfn is default_batchify_fn:
                bfn = _numpy_batchify  # NDArray assembly stays parent-side
            # children must never attach the chip this process holds:
            # _mp_init pins the CPU backend inside every worker (also
            # the ones the pool respawns later) BEFORE it unpickles the
            # dataset, so no parent-side env juggling is needed here
            self._pool = ctx.Pool(
                self._num_workers, initializer=_mp_init,
                initargs=(pickle.dumps(self._dataset), bfn,
                          self._worker_transport,
                          _chaos.export_plans("dataloader.worker")
                          if _chaos._ACTIVE else ()))
        return self._pool

    def _result_or_dead(self, res, pool, worker_pids):
        """``res.get`` sliced into short waits that watch worker
        liveness: a dead child (its pid reaped from, or respawned out
        of, ``pool._pool``) raises :class:`WorkerDied` NOW — its task
        is lost and the result would otherwise only surface as an
        opaque timeout a full ``self._timeout`` later."""
        import multiprocessing as mp

        deadline = time.monotonic() + self._timeout
        while True:
            remaining = deadline - time.monotonic()
            try:
                return res.get(min(0.5, max(remaining, 0.01)))
            except mp.TimeoutError:
                current = {w.pid for w in pool._pool}
                dead = sorted(
                    (worker_pids - current)
                    | {w.pid for w in pool._pool if not w.is_alive()})
                if dead:
                    raise WorkerDied(
                        f"DataLoader worker process(es) {dead} died "
                        f"abnormally; their in-flight batches are lost "
                        f"(the pool is discarded — recreate the "
                        f"iterator to continue)", worker=dead[0]) \
                        from None
                if remaining <= 0:
                    raise MXNetError(
                        f"DataLoader worker timed out after "
                        f"{self._timeout}s") from None

    def _process_iter(self, start: int = 0):
        """Strict-order prefetching over the persistent spawn pool;
        worker exceptions re-raise in the consumer (pickled through).
        In-flight shm results are reclaimed on ANY exit (early break,
        worker error, timeout) — the workers detach their shm
        registration, so an undrained descriptor would otherwise leak
        its /dev/shm segment until reboot."""
        from collections import deque

        pool = self._get_pool()
        worker_pids = {w.pid for w in pool._pool}
        batches = list(self._batch_sampler)[start:]
        window = max(self._prefetch, self._num_workers, 2)
        pending: deque = deque()
        it = iter(batches)
        timed_out = False
        died = False
        try:
            for _ in range(min(window, len(batches))):
                pending.append(pool.apply_async(_mp_make_batch,
                                                (next(it),)))
            while pending:
                res = pending.popleft()
                t0 = time.perf_counter() if _tracing.active() else None
                try:
                    out = self._result_or_dead(res, pool, worker_pids)
                except BaseException as e:
                    # the popped result may still arrive later and hold
                    # a shm segment — put it back so the drain sees it
                    pending.appendleft(res)
                    timed_out = True
                    if isinstance(e, WorkerDied):
                        # the respawned pool would re-lose the dead
                        # worker's task; start clean next iteration
                        died = True
                        try:
                            pool.terminate()
                        finally:
                            self._pool = None
                    raise
                if t0 is not None:
                    _observe_data_wait(t0)
                try:
                    pending.append(pool.apply_async(_mp_make_batch,
                                                    (next(it),)))
                except StopIteration:
                    pass
                yield self._wrap_np(out)
        finally:
            # healthy teardown (early break / epoch end) waits out slow
            # but live batches; after a worker timeout/crash, cap the
            # wait — those results mostly never arrive (and after a
            # terminated pool they NEVER arrive: shortest cap)
            _drain_shm(pending,
                       2 if died
                       else min(self._timeout, 15) if timed_out
                       else self._timeout)

    @staticmethod
    def _wrap_np(out):
        if isinstance(out, tuple):
            if len(out) == 3 and out[0] == "__shm__":
                return _shm_unpack(out[1], out[2])
            return tuple(DataLoader._wrap_np(o) for o in out)
        if isinstance(out, np.ndarray):
            return nd_array(out)
        return out

    def __del__(self):
        pool = getattr(self, "_pool", None)
        if pool is not None:
            try:
                pool.terminate()
            except Exception:
                pass

    def _threaded_iter(self, start: int = 0):
        """Prefetching iterator with N REAL worker threads (reference
        semantics: num_workers parallel batch producers).  Workers pull
        batch indices from a shared queue and publish into a reorder
        buffer keyed by batch position, so results stream strictly in
        sampler order; numpy/cv2/TF decode inside `__getitem__` releases
        the GIL, which is where the parallelism pays.

        A worker thread that dies without publishing (chaos-injected, or
        a C extension taking the thread down) surfaces as
        :class:`WorkerDied` at the consumer — the liveness check below —
        instead of a full-timeout hang for a batch that can never
        arrive."""
        batches = list(self._batch_sampler)[start:]
        n_workers = self._num_workers
        window = max(self._prefetch, n_workers, 2)  # in-flight bound
        task_q: "queue.Queue" = queue.Queue()
        # mxsan: the reorder buffer is shared by every worker and the
        # consumer; all access must hold done_cv (the tier-1 shutdown
        # regression test runs this loop under the sanitizer)
        done: dict = _mxsan.track(
            {}, "gluon.data.DataLoader._threaded_iter.done")
        done_cv = threading.Condition()
        stop = threading.Event()

        def worker():
            while True:
                item = task_q.get()
                if item is None or stop.is_set():  # sentinel: shut down
                    return
                pos, indices = item
                if _chaos._ACTIVE:
                    try:
                        if _chaos.check("dataloader.worker") == "die":
                            return  # abnormal exit: publish NOTHING
                    except BaseException as e:
                        with done_cv:
                            done[pos] = ("err", e)
                            done_cv.notify_all()
                        continue
                try:
                    result = ("ok", self._make_batch(indices))
                except BaseException as e:  # propagate to consumer
                    result = ("err", e)
                with done_cv:
                    done[pos] = result
                    done_cv.notify_all()

        next_submit = min(window, len(batches))
        for pos in range(next_submit):  # seed the prefetch window
            task_q.put((pos, batches[pos]))
        threads = [threading.Thread(target=worker, daemon=True,
                                    name=f"mx-dataloader-worker-{i}")
                   for i in range(n_workers)]
        for t in threads:
            t.start()
        try:
            for pos in range(len(batches)):
                t0 = time.perf_counter() if _tracing.active() else None
                deadline = time.monotonic() + self._timeout
                with done_cv:
                    while pos not in done:
                        dead = [t.name for t in threads
                                if not t.is_alive()]
                        if dead:
                            raise WorkerDied(
                                f"DataLoader worker thread(s) "
                                f"{dead} exited abnormally; batch "
                                f"{pos + start} will never arrive",
                                worker=dead[0])
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            raise MXNetError(
                                f"DataLoader worker timed out after "
                                f"{self._timeout}s (batch "
                                f"{pos + start})")
                        done_cv.wait(timeout=min(0.2, remaining))
                    kind, payload = done.pop(pos)
                if kind == "err":
                    raise payload
                if t0 is not None:
                    _observe_data_wait(t0)
                if next_submit < len(batches):  # top up the window
                    task_q.put((next_submit, batches[next_submit]))
                    next_submit += 1
                yield payload
        finally:
            stop.set()
            for _ in threads:
                task_q.put(None)

    def __len__(self):
        return len(self._batch_sampler)
