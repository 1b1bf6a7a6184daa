"""Spawn-based process-pool DataLoader (worker_pool="process"): strict
sampler order, persistent pool across epochs, error propagation, and the
GIL escape for pure-python __getitem__ (docs/data.md crossover notes).
Spawn (not fork) so no PjRt/TPU client is inherited by workers."""
import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.data import ArrayDataset, DataLoader


class _PurePython:
    """CPU-bound pure-python __getitem__ (holds the GIL)."""

    def __init__(self, n=24):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        acc = 0
        for k in range(2000):
            acc = (acc + i * k) % 9973
        return np.array([i, acc], np.float32)


class _Failing:
    def __len__(self):
        return 6

    def __getitem__(self, i):
        if i == 3:
            raise ValueError("boom-3")
        return np.zeros(2, np.float32)


class _PlatformAtUnpickle:
    """Records, when a worker unpickles it, what JAX_PLATFORMS was."""

    def __init__(self):
        self.seen = None

    def __len__(self):
        return 4

    def __getitem__(self, i):
        return np.array([i if self.seen == "cpu" else -1], np.float32)

    def __setstate__(self, state):
        import os

        self.seen = os.environ.get("JAX_PLATFORMS")


def test_dataset_unpickled_after_cpu_pin(monkeypatch):
    """Workers are CPU-only and the parent holds the chip: a pickled
    NDArray rebuilds its jax array on the default device, so the dataset
    must be unpickled only after _mp_init has pinned the CPU backend —
    whatever platform list the parent's environment names."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    dl = DataLoader(_PlatformAtUnpickle(), batch_size=4, num_workers=1,
                    worker_pool="process")
    (batch,) = list(dl)
    np.testing.assert_array_equal(batch.asnumpy()[:, 0], [0, 1, 2, 3])


def test_process_pool_order_and_reuse():
    x = np.arange(80, dtype=np.float32).reshape(20, 4)
    y = np.arange(20, dtype=np.float32)
    dl = DataLoader(ArrayDataset(x, y), batch_size=4, num_workers=2,
                    worker_pool="process")
    for _epoch in range(2):  # persistent pool: second epoch reuses it
        got = list(dl)
        assert len(got) == 5
        xa, ya = got[0]
        np.testing.assert_array_equal(xa.asnumpy(), x[:4])
        np.testing.assert_array_equal(ya.asnumpy(), y[:4])
        xl, _ = got[-1]
        np.testing.assert_array_equal(xl.asnumpy(), x[16:])


def test_process_pool_propagates_worker_errors():
    dl = DataLoader(_Failing(), batch_size=2, num_workers=2,
                    worker_pool="process")
    with pytest.raises(ValueError, match="boom-3"):
        list(dl)


def test_process_pool_pure_python_dataset():
    dl = DataLoader(_PurePython(), batch_size=6, num_workers=2,
                    worker_pool="process")
    out = list(dl)
    assert len(out) == 4
    first = out[0].asnumpy()
    np.testing.assert_array_equal(first[:, 0], [0, 1, 2, 3, 4, 5])


def test_invalid_worker_pool_rejected():
    with pytest.raises(MXNetError, match="worker_pool"):
        DataLoader(_PurePython(), batch_size=2, worker_pool="greenlet")


def test_process_pool_pipe_transport_matches_shm():
    x = np.arange(48, dtype=np.float32).reshape(12, 4)
    y = np.arange(12, dtype=np.float32)
    for transport in ("shm", "pipe"):
        dl = DataLoader(ArrayDataset(x, y), batch_size=3, num_workers=2,
                        worker_pool="process", worker_transport=transport)
        got = list(dl)
        assert len(got) == 4
        xa, ya = got[1]
        np.testing.assert_array_equal(xa.asnumpy(), x[3:6])
        np.testing.assert_array_equal(ya.asnumpy(), y[3:6])


def test_invalid_worker_transport_rejected():
    with pytest.raises(MXNetError, match="worker_transport"):
        DataLoader(_PurePython(), batch_size=2, worker_transport="rdma")


def test_shm_segments_reclaimed_on_early_break():
    """Abandoning the iterator mid-epoch must not leak /dev/shm
    segments from in-flight prefetched batches."""
    import glob

    def _segs():
        return set(glob.glob("/dev/shm/psm_*"))

    x = np.arange(160, dtype=np.float32).reshape(40, 4)
    dl = DataLoader(ArrayDataset(x, x[:, 0]), batch_size=4,
                    num_workers=2, worker_pool="process")
    before = _segs()
    it = iter(dl)
    next(it)
    it.close()  # generator finally -> _drain_shm
    del it
    import time
    time.sleep(1)
    leaked = _segs() - before
    assert not leaked, leaked
