"""Data iterator tests (model: tests/python/unittest/test_io.py)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.io import (CSVIter, DataBatch, DataDesc, NDArrayIter,
                          PrefetchingIter, ResizeIter)
from mxnet_tpu.test_utils import assert_almost_equal


def test_ndarrayiter_basic():
    data = np.arange(40, dtype="float32").reshape(10, 4)
    label = np.arange(10, dtype="float32")
    it = NDArrayIter(data, label, batch_size=5)
    batches = list(it)
    assert len(batches) == 2
    assert batches[0].data[0].shape == (5, 4)
    assert_almost_equal(batches[0].data[0], data[:5])
    assert_almost_equal(batches[1].label[0], label[5:])
    # reset + reiterate
    it.reset()
    assert len(list(it)) == 2


def test_ndarrayiter_pad_and_discard():
    data = np.arange(14, dtype="float32").reshape(7, 2)
    it = NDArrayIter(data, None, batch_size=4, last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 2
    assert batches[1].pad == 1
    assert batches[1].data[0].shape == (4, 2)  # padded by wrap-around
    it = NDArrayIter(data, None, batch_size=4, last_batch_handle="discard")
    assert len(list(it)) == 1


def test_ndarrayiter_shuffle_covers_all():
    data = np.arange(16, dtype="float32").reshape(16, 1)
    it = NDArrayIter(data, None, batch_size=4, shuffle=True)
    seen = np.concatenate([b.data[0].asnumpy().ravel() for b in it])
    assert sorted(seen.tolist()) == list(range(16))


def test_ndarrayiter_dict_input():
    it = NDArrayIter({"a": np.zeros((6, 2), "float32"),
                      "b": np.ones((6, 3), "float32")}, None, batch_size=3)
    names = sorted(d.name for d in it.provide_data)
    assert names == ["a", "b"]


def test_provide_data_descs():
    it = NDArrayIter(np.zeros((8, 3, 4, 4), "float32"),
                     np.zeros(8, "float32"), batch_size=2)
    d = it.provide_data[0]
    assert isinstance(d, DataDesc)
    assert d.shape == (2, 3, 4, 4)
    assert it.provide_label[0].name == "softmax_label"


def test_csviter(tmp_path):
    data = np.random.rand(10, 6).astype("float32")
    labels = np.arange(10, dtype="float32")
    data_csv = str(tmp_path / "data.csv")
    label_csv = str(tmp_path / "label.csv")
    np.savetxt(data_csv, data, delimiter=",")
    np.savetxt(label_csv, labels, delimiter=",")
    it = CSVIter(data_csv=data_csv, data_shape=(6,), label_csv=label_csv,
                 batch_size=5)
    batches = list(it)
    assert len(batches) == 2
    assert_almost_equal(batches[0].data[0], data[:5], rtol=1e-5, atol=1e-6)


def test_resizeiter():
    data = np.zeros((8, 2), "float32")
    base = NDArrayIter(data, None, batch_size=4)
    it = ResizeIter(base, size=5)
    assert len(list(it)) == 5  # wraps around the underlying 2 batches
    it.reset()
    assert len(list(it)) == 5


def test_prefetching_iter():
    data = np.arange(24, dtype="float32").reshape(12, 2)
    base = NDArrayIter(data, None, batch_size=4)
    it = PrefetchingIter(base)
    batches = list(it)
    assert len(batches) == 3
    assert_almost_equal(batches[0].data[0], data[:4])
    it.reset()
    assert len(list(it)) == 3


def test_image_record_iter(tmp_path):
    cv2 = pytest.importorskip("cv2", reason="needs an image encoder")


def test_image_record_iter_synthetic(tmp_path):
    # pack synthetic images with the recordio writer + image.imencode
    from mxnet_tpu import recordio
    from mxnet_tpu import image as img_mod
    from mxnet_tpu.io import ImageRecordIter

    try:
        enc = img_mod.imencode(np.zeros((8, 8, 3), np.uint8))
    except Exception:
        pytest.skip("no image encoder available in this environment")
    path = str(tmp_path / "data.rec")
    rec = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    for i in range(6):
        arr = rng.randint(0, 255, size=(10, 12, 3), dtype=np.uint8)
        packed = recordio.pack_img(
            recordio.IRHeader(0, float(i % 3), i, 0), arr, quality=90)
        rec.write(packed)
    rec.close()

    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                         batch_size=3)
    batches = list(it)
    assert len(batches) == 2
    assert batches[0].data[0].shape == (3, 3, 8, 8)
    assert batches[0].label[0].shape == (3,)


def test_misc_api_modules():
    """engine/runtime/visualization/name/attribute parity surfaces."""
    import mxnet_tpu.engine as engine
    import mxnet_tpu.runtime as runtime

    with engine.bulk(10):
        y = mx.nd.ones((2, 2)) + 1
    assert y.asnumpy().sum() == 8
    prev = engine.set_bulk_size(20)
    engine.set_bulk_size(prev)

    feats = runtime.Features()
    assert feats.is_enabled("JAX")
    assert any(f.name == "TPU" for f in runtime.feature_list())

    from mxnet_tpu import symbol as sym
    from mxnet_tpu.name import Prefix

    with Prefix("mynet_"):
        s = sym.FullyConnected(sym.var("data"), num_hidden=3)
    assert s.name.startswith("mynet_")

    from mxnet_tpu.visualization import plot_network, print_summary

    net = sym.FullyConnected(sym.var("data"), num_hidden=3, name="fc")
    dot = plot_network(net)
    assert "fc" in str(dot)
    print_summary(net, shape={"data": (2, 5)})

    from mxnet_tpu.attribute import AttrScope

    with AttrScope(ctx_group="dev1") as scope:
        assert scope.get(None) == {"ctx_group": "dev1"}


def test_monitor_with_module():
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.module import Module
    from mxnet_tpu.monitor import Monitor

    x = np.random.randn(16, 5).astype("float32")
    y = np.random.randint(0, 3, 16).astype("float32")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.var("data"), num_hidden=3, name="fc"),
        name="softmax")
    mod = Module(net, context=mx.cpu())
    it = NDArrayIter(x, y, batch_size=16)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mon = Monitor(interval=1, pattern=".*weight.*")
    mod.install_monitor(mon)
    mon.tic()
    batch = next(iter(it))
    mod.forward(batch, is_train=True)
    stats = mon.toc()
    assert any("fc_weight" in k for (_, k, _) in stats)


def test_recordio_chunked_large_records(tmp_path):
    """Regression: records longer than the 29-bit length field must be
    chunk-chained (cflag 1/2/3), not silently truncated.  A small
    _max_chunk exercises the same code path without 512MB fixtures."""
    from mxnet_tpu import recordio

    path = str(tmp_path / "chunked.rec")
    w = recordio.MXRecordIO(path, "w")
    w._max_chunk = 64
    # payloads longer than the chunk size, incl. embedded magic bytes
    magic = (0x3ED7230A).to_bytes(4, "little")
    payloads = [b"x" * 200, magic * 50 + b"tail", b"short", b"y" * 64 * 3]
    for p in payloads:
        w.write(p)
    w.close()

    r = recordio.MXRecordIO(path, "r")
    for p in payloads:
        assert r.read() == p
    assert r.read() is None
    r.close()

    # the native reader joins the same chunk chain
    from mxnet_tpu import lib
    if lib.available():
        nr = lib.NativeRecordReader(path)
        for p in payloads:
            assert nr.read() == p
        assert nr.read() is None
        nr.close()


def test_recordio_truncated_chunk_chain_raises(tmp_path):
    """EOF mid-chunk-chain must fail loud, not hand back a partial record."""
    from mxnet_tpu import lib, recordio
    from mxnet_tpu.base import MXNetError

    path = str(tmp_path / "trunc.rec")
    w = recordio.MXRecordIO(path, "w")
    w._max_chunk = 16
    w.write(b"z" * 50)  # 4 chunks: cflag 1,2,2,3
    w.close()
    # cut the file after the second chunk (2 * (8 + 16) bytes)
    with open(path, "r+b") as f:
        f.truncate(48)
    r = recordio.MXRecordIO(path, "r")
    with pytest.raises(MXNetError, match="truncated"):
        r.read()
    r.close()
    if lib.available():
        nr = lib.NativeRecordReader(path)
        with pytest.raises(MXNetError, match="truncated"):
            nr.read()
        nr.close()


def test_recordio_truncated_final_chunk_payload(tmp_path):
    """Truncation inside a chunk PAYLOAD (not between chunks) must also
    raise, matching the native reader."""
    from mxnet_tpu import recordio
    from mxnet_tpu.base import MXNetError
    path = str(tmp_path / "t.rec")
    w = recordio.MXRecordIO(path, "w")
    w._max_chunk = 16
    w.write(b"q" * 50)
    w.close()
    import os
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 3)
    r = recordio.MXRecordIO(path, "r")
    import pytest as _pytest
    with _pytest.raises(MXNetError, match="truncated"):
        r.read()
    r.close()


# ---------------------------------------------------------------------------
# tools/: parse_log.py + bandwidth.py (ref: tools/parse_log.py,
# tools/bandwidth/)
# ---------------------------------------------------------------------------

def test_parse_log_tool(tmp_path):
    import subprocess
    import sys

    log = tmp_path / "train.log"
    log.write_text(
        "INFO Epoch[0] Batch [50]\tSpeed: 2461.16 samples/sec\taccuracy=0.5\n"
        "INFO Epoch[0] Batch [100]\tSpeed: 2400.00 samples/sec\taccuracy=0.6\n"
        "INFO Epoch[0] Train-accuracy=0.612000\n"
        "INFO Epoch[0] Validation-accuracy=0.587000\n"
        "INFO Epoch[0] Time cost=12.345\n"
        "INFO Epoch[1] Train-accuracy=0.701000\n"
        "INFO Epoch[1] Time cost=11.000\n")
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "parse_log.py")
    r = subprocess.run([sys.executable, tool, str(log), "--format", "csv"],
                      capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    lines = r.stdout.strip().splitlines()
    assert lines[0] == "epoch,speed,time-s,train-accuracy,val-accuracy"
    assert lines[1].startswith("0,2430.58,12.345,0.612,0.587")
    assert lines[2].startswith("1,,11,0.701,")
    r = subprocess.run([sys.executable, tool, str(log)],
                      capture_output=True, text=True, timeout=60)
    assert r.returncode == 0 and "| epoch" in r.stdout


def test_bandwidth_tool_mesh():
    """In-graph allreduce bandwidth across the virtual 8-device mesh."""
    import subprocess
    import sys

    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "bandwidth.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, tool, "--sizes", "1", "--iters", "2"],
        capture_output=True, text=True, timeout=240, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "mesh-psum x8" in r.stdout


# ---------------------------------------------------------------------------
# LibSVMIter (ref: src/io/iter_libsvm.cc) + ImageIter/ImageDetIter
# (ref: python/mxnet/image/{image,detection}.py)
# ---------------------------------------------------------------------------

def test_libsvm_iter(tmp_path):
    f = tmp_path / "train.libsvm"
    f.write_text("1 0:1.5 3:2.0\n"
                 "0 1:1.0\n"
                 "1 2:0.5 3:0.5\n")
    it = mx.io.LibSVMIter(data_libsvm=str(f), data_shape=(4,),
                          batch_size=2)
    b1 = it.next()
    assert b1.data[0].stype == "csr"
    np.testing.assert_allclose(
        b1.data[0].todense().asnumpy(),
        [[1.5, 0, 0, 2.0], [0, 1.0, 0, 0]])
    np.testing.assert_allclose(b1.label[0].asnumpy(), [1, 0])
    b2 = it.next()  # wraps to fill the last batch
    assert b2.pad == 1
    np.testing.assert_allclose(
        b2.data[0].todense().asnumpy()[0], [0, 0, 0.5, 0.5])
    with pytest.raises(StopIteration):
        it.next()
    it.reset()
    assert it.next().pad == 0
    # sparse dot consumes the batch directly
    w = mx.nd.ones((4, 3))
    out = mx.nd.sparse.dot(b1.data[0], w)
    np.testing.assert_allclose(out.asnumpy()[0], [3.5, 3.5, 3.5])


def _write_img_rec(tmp_path, n=6, label_width=1, det=False):
    from mxnet_tpu import recordio as rio
    from mxnet_tpu.image import imencode

    path = str(tmp_path / "data.rec")
    rec = rio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        img = (rng.rand(12, 10, 3) * 255).astype(np.uint8)
        if det:
            # packed object labels: header(2) + one or two boxes of width 5
            nobj = 1 + (i % 2)
            objs = []
            for b in range(nobj):
                objs += [float(i % 3), 0.1, 0.1, 0.6, 0.7]
            label = np.asarray([2, 5] + objs, np.float32)
        else:
            label = float(i % 3) if label_width == 1 else \
                np.arange(label_width, dtype=np.float32)
        h = rio.IRHeader(0, label, i, 0)
        rec.write(rio.pack_img(h, img, quality=90))
    rec.close()
    return path


def test_image_iter_rec(tmp_path):
    try:
        from mxnet_tpu.image import imencode  # noqa: F401
        _ = imencode(np.zeros((4, 4, 3), np.uint8))
    except Exception:
        pytest.skip("no image encoder available")
    from mxnet_tpu.image import CreateAugmenter, ImageIter

    path = _write_img_rec(tmp_path)
    it = ImageIter(batch_size=4, data_shape=(3, 8, 8),
                   path_imgrec=path,
                   aug_list=CreateAugmenter((3, 8, 8)))
    batch = it.next()
    assert batch.data[0].shape == (4, 3, 8, 8)
    assert batch.label[0].shape == (4,)
    np.testing.assert_allclose(batch.label[0].asnumpy(), [0, 1, 2, 0])
    b2 = it.next()  # 2 remaining + 2 pad
    assert b2.pad == 2
    it.reset()
    assert it.next().pad == 0


def test_image_iter_imglist(tmp_path):
    try:
        from mxnet_tpu.image import imencode
        _ = imencode(np.zeros((4, 4, 3), np.uint8))
    except Exception:
        pytest.skip("no image encoder available")
    from mxnet_tpu.image import ImageIter

    rng = np.random.RandomState(1)
    names = []
    for i in range(3):
        img = (rng.rand(9, 9, 3) * 255).astype(np.uint8)
        from mxnet_tpu.image import imencode

        (tmp_path / f"im{i}.jpg").write_bytes(imencode(img))
        names.append(f"im{i}.jpg")
    lst = tmp_path / "train.lst"
    lst.write_text("".join(f"{i}\t{float(i)}\t{n}\n"
                           for i, n in enumerate(names)))
    it = ImageIter(batch_size=3, data_shape=(3, 8, 8),
                   path_imglist=str(lst), path_root=str(tmp_path))
    b = it.next()
    assert b.data[0].shape == (3, 3, 8, 8)
    np.testing.assert_allclose(b.label[0].asnumpy(), [0, 1, 2])


def test_image_det_iter(tmp_path):
    try:
        from mxnet_tpu.image import imencode
        _ = imencode(np.zeros((4, 4, 3), np.uint8))
    except Exception:
        pytest.skip("no image encoder available")
    from mxnet_tpu.image import ImageDetIter

    path = _write_img_rec(tmp_path, det=True)
    it = ImageDetIter(batch_size=3, data_shape=(3, 8, 8),
                      path_imgrec=path)
    b = it.next()
    assert b.data[0].shape == (3, 3, 8, 8)
    lab = b.label[0].asnumpy()
    assert lab.shape == (3, 2, 5)  # max 2 objects, width 5
    # sample 0 has one object, row 1 padded with -1
    np.testing.assert_allclose(lab[0, 0], [0, 0.1, 0.1, 0.6, 0.7],
                               rtol=1e-5)
    assert (lab[0, 1] == -1).all()
    # sample 1 has two objects
    assert (lab[1, 1] != -1).any()


def test_det_augmenters_keep_boxes_aligned(tmp_path):
    """DetHorizontalFlipAug mirrors boxes with the image; force-resize
    leaves relative coords invariant (plain Augmenters are rejected)."""
    from mxnet_tpu.image import (CreateDetAugmenter, DetHorizontalFlipAug,
                                 DetForceResizeAug)
    from mxnet_tpu.ndarray import array as nd_array

    img = np.zeros((10, 20, 3), np.float32)
    img[:, :10] = 1.0  # left half bright
    boxes = np.array([[0.0, 0.1, 0.2, 0.4, 0.8]], np.float32)
    flip = DetHorizontalFlipAug(p=1.1)  # always flip
    out, fboxes = flip(nd_array(img), boxes)
    # image mirrored: bright half now on the right
    assert out.asnumpy()[0, -1, 0] == 1.0 and out.asnumpy()[0, 0, 0] == 0.0
    np.testing.assert_allclose(fboxes[0], [0.0, 0.6, 0.2, 0.9, 0.8],
                               rtol=1e-6)
    rs = DetForceResizeAug((8, 8))
    out2, rboxes = rs(nd_array(img), boxes)
    assert out2.shape == (8, 8, 3)
    np.testing.assert_allclose(rboxes, boxes)  # relative coords invariant
    import pytest as _pytest

    from mxnet_tpu.image import CenterCropAug, ImageDetIter
    with _pytest.raises(Exception, match="DetAugmenter"):
        ImageDetIter(batch_size=1, data_shape=(3, 8, 8),
                     path_imgrec="/nonexistent.rec",
                     aug_list=[CenterCropAug((8, 8))])
