"""Minimal NDArray/op C ABI (src/ndarray_capi.cc + capi_bridge.py).

Round-4 verdict item #8: the N14 row needed either a minimal C surface
or a permanent close-out.  This is the surface, exercised two ways:

  * in-process: ctypes drives the flat C ABI from this pytest process
    (the interpreter is already up, MXCapiInit attaches), covering
    create / copy-in / invoke / copy-out / shape / dtype / free and the
    error path;
  * standalone: a real C program is compiled against the .so +
    libpython, runs in a subprocess with an EMBEDDED interpreter, and
    performs the same round-trip — the cpp-package-style consumer story
    (ref: include/mxnet/c_api.h + cpp-package/ in the reference tree).
"""
import ctypes
import os
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

from mxnet_tpu import lib as native

pytestmark = pytest.mark.skipif(not native.capi_available(),
                                reason="c-api library unavailable")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _capi():
    lib = native.capi_get()
    lib.MXCapiInit.restype = ctypes.c_int
    native.capi_check(lib.MXCapiInit())
    return lib


def _create(lib, shape, dtype="float32"):
    arr = (ctypes.c_int64 * len(shape))(*shape)
    h = ctypes.c_void_p()
    native.capi_check(lib.MXNDArrayCreate(arr, len(shape),
                                          dtype.encode(),
                                          ctypes.byref(h)))
    return h


def test_create_copy_roundtrip_and_shape():
    lib = _capi()
    h = _create(lib, (2, 3))
    data = np.arange(6, dtype=np.float32).reshape(2, 3)
    buf = data.tobytes()
    native.capi_check(lib.MXNDArraySyncCopyFromCPU(
        h, buf, ctypes.c_uint64(len(buf))))

    ndim = ctypes.c_int()
    shape = (ctypes.c_int64 * 8)()
    native.capi_check(lib.MXNDArrayGetShape(
        h, ctypes.byref(ndim), shape, 8))
    assert ndim.value == 2 and tuple(shape[:2]) == (2, 3)

    dt = ctypes.create_string_buffer(32)
    native.capi_check(lib.MXNDArrayGetDType(h, dt, 32))
    assert dt.value == b"float32"

    out = ctypes.create_string_buffer(len(buf))
    native.capi_check(lib.MXNDArraySyncCopyToCPU(
        h, out, ctypes.c_uint64(len(buf))))
    np.testing.assert_array_equal(
        np.frombuffer(out.raw, np.float32).reshape(2, 3), data)
    native.capi_check(lib.MXNDArrayFree(h))


def test_imperative_invoke_with_attrs():
    lib = _capi()
    h = _create(lib, (2, 3))
    data = np.arange(6, dtype=np.float32).reshape(2, 3)
    native.capi_check(lib.MXNDArraySyncCopyFromCPU(
        h, data.tobytes(), ctypes.c_uint64(data.nbytes)))

    def invoke(name, handles, attrs):
        ins = (ctypes.c_void_p * len(handles))(
            *[hh.value for hh in handles])
        keys = (ctypes.c_char_p * max(len(attrs), 1))(
            *[k.encode() for k in attrs])
        vals = (ctypes.c_char_p * max(len(attrs), 1))(
            *[v.encode() for v in attrs.values()])
        outs = (ctypes.c_void_p * 4)()
        nout = ctypes.c_int()
        native.capi_check(lib.MXImperativeInvoke(
            name.encode(), ins, len(handles), keys, vals, len(attrs),
            outs, ctypes.byref(nout), 4))
        return [ctypes.c_void_p(outs[i]) for i in range(nout.value)]

    def read(hh, shape):
        n = int(np.prod(shape)) * 4
        out = ctypes.create_string_buffer(n)
        native.capi_check(lib.MXNDArraySyncCopyToCPU(
            hh, out, ctypes.c_uint64(n)))
        return np.frombuffer(out.raw, np.float32).reshape(shape)

    added = invoke("elemwise_add", [h, h], {})
    assert len(added) == 1
    np.testing.assert_allclose(read(added[0], (2, 3)), data * 2)

    # attrs arrive as reference-style strings and get literal-parsed
    tr = invoke("transpose", [h], {"axes": "(1, 0)"})
    np.testing.assert_allclose(read(tr[0], (3, 2)), data.T)

    for hh in added + tr + [h]:
        native.capi_check(lib.MXNDArrayFree(hh))


def test_output_overflow_errors_instead_of_truncating():
    """More outputs than the caller's buffer is an ERROR (with the true
    count reported) — not a silent DECREF of the overflow: re-invoking
    re-executes the op, so dropped results would be unrecoverable."""
    lib = _capi()
    h = _create(lib, (2, 3))
    data = np.arange(6, dtype=np.float32).reshape(2, 3)
    native.capi_check(lib.MXNDArraySyncCopyFromCPU(
        h, data.tobytes(), ctypes.c_uint64(data.nbytes)))
    ins = (ctypes.c_void_p * 2)(h.value, h.value)
    keys = (ctypes.c_char_p * 1)()
    vals = (ctypes.c_char_p * 1)()
    outs = (ctypes.c_void_p * 1)()
    nout = ctypes.c_int()
    rc = lib.MXImperativeInvoke(b"elemwise_add", ins, 2, keys, vals, 0,
                                outs, ctypes.byref(nout), 0)
    assert rc != 0
    assert nout.value == 1  # the true count, so the caller can resize
    lib.MXCapiGetLastError.restype = ctypes.c_char_p
    msg = lib.MXCapiGetLastError().decode()
    assert "larger buffer" in msg, msg
    # retry with room succeeds and yields the actual result
    rc = lib.MXImperativeInvoke(b"elemwise_add", ins, 2, keys, vals, 0,
                                outs, ctypes.byref(nout), 1)
    assert rc == 0 and nout.value == 1
    got = ctypes.create_string_buffer(data.nbytes)
    native.capi_check(lib.MXNDArraySyncCopyToCPU(
        ctypes.c_void_p(outs[0]), got, ctypes.c_uint64(data.nbytes)))
    np.testing.assert_allclose(
        np.frombuffer(got.raw, np.float32).reshape(2, 3), data * 2)
    for hh in (ctypes.c_void_p(outs[0]), h):
        native.capi_check(lib.MXNDArrayFree(hh))


def test_error_surface_is_loud():
    lib = _capi()
    h = _create(lib, (2, 2))
    rc = lib.MXNDArraySyncCopyFromCPU(h, b"xx", ctypes.c_uint64(2))
    assert rc != 0
    lib.MXCapiGetLastError.restype = ctypes.c_char_p
    msg = lib.MXCapiGetLastError().decode()
    assert "bytes" in msg, msg
    native.capi_check(lib.MXNDArrayFree(h))


_C_CONSUMER = r"""
#include <stdint.h>
#include <stdio.h>
#include <string.h>

extern int MXCapiInit(void);
extern const char* MXCapiGetLastError(void);
extern int MXNDArrayCreate(const int64_t*, int, const char*, void**);
extern int MXNDArrayFree(void*);
extern int MXNDArraySyncCopyFromCPU(void*, const void*, uint64_t);
extern int MXNDArraySyncCopyToCPU(void*, void*, uint64_t);
extern int MXImperativeInvoke(const char*, void**, int, const char**,
                              const char**, int, void**, int*, int);

#define CHECK(x) if ((x) != 0) { \
    fprintf(stderr, "FAIL: %s\n", MXCapiGetLastError()); return 1; }

int main(void) {
  CHECK(MXCapiInit());
  int64_t shape[2] = {2, 2};
  void *a = NULL;
  CHECK(MXNDArrayCreate(shape, 2, "float32", &a));
  float in[4] = {1.f, 2.f, 3.f, 4.f};
  CHECK(MXNDArraySyncCopyFromCPU(a, in, sizeof(in)));
  void* ins[2] = {a, a};
  void* outs[1];
  int nout = 0;
  CHECK(MXImperativeInvoke("elemwise_add", ins, 2, NULL, NULL, 0,
                           outs, &nout, 1));
  float got[4];
  CHECK(MXNDArraySyncCopyToCPU(outs[0], got, sizeof(got)));
  for (int i = 0; i < 4; ++i)
    if (got[i] != 2.f * in[i]) { fprintf(stderr, "BAD VALUE\n"); return 1; }
  CHECK(MXNDArrayFree(outs[0]));
  CHECK(MXNDArrayFree(a));
  printf("CAPI_CONSUMER_OK\n");
  return 0;
}
"""


def test_standalone_c_consumer(tmp_path):
    """Compile a real C program against the .so and run it with an
    embedded interpreter — no Python on the consumer side at all."""
    so = native._CAPI.so_path
    src = tmp_path / "consumer.c"
    src.write_text(_C_CONSUMER)
    exe = tmp_path / "consumer"
    libdir = sysconfig.get_config_var("LIBDIR") or "/usr/local/lib"
    ver = sysconfig.get_config_var("LDVERSION") or "3.12"
    cc = ["gcc", str(src), "-o", str(exe), so,
          f"-L{libdir}", f"-lpython{ver}",
          f"-Wl,-rpath,{libdir}", f"-Wl,-rpath,{os.path.dirname(so)}"]
    built = subprocess.run(cc, capture_output=True, text=True)
    assert built.returncode == 0, built.stderr[-2000:]
    env = dict(os.environ)
    # the embedded interpreter must find the package and stay on CPU
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_DEFAULT_CONTEXT"] = "cpu"
    p = subprocess.run([str(exe)], capture_output=True, text=True,
                       timeout=240, env=env)
    assert p.returncode == 0, (p.stdout + p.stderr)[-2000:]
    assert "CAPI_CONSUMER_OK" in p.stdout


def test_deploy_serving_from_c(tmp_path):
    """The full cpp-package-predictor equivalence: export an artifact
    in Python, then load and serve it through the flat C ABI
    (MXDeployLoad/Run) — NDArray handles in, handles out."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd as mxnd
    from mxnet_tpu.contrib import deploy
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu", in_units=4))
        net.add(nn.Dense(3, in_units=8))
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    x_np = np.random.RandomState(0).rand(2, 4).astype("float32")
    ref = net(mxnd.array(x_np)).asnumpy()
    deploy.export_model(net, str(tmp_path), [mxnd.array(x_np)])

    lib = _capi()
    served = ctypes.c_void_p()
    native.capi_check(lib.MXDeployLoad(str(tmp_path).encode(),
                                       ctypes.byref(served)))
    h = _create(lib, (2, 4))
    native.capi_check(lib.MXNDArraySyncCopyFromCPU(
        h, x_np.tobytes(), ctypes.c_uint64(x_np.nbytes)))
    outs = (ctypes.c_void_p * 4)()
    nout = ctypes.c_int()
    native.capi_check(lib.MXDeployRun(
        served, (ctypes.c_void_p * 1)(h), 1, ctypes.c_uint64(0), outs,
        ctypes.byref(nout), 4))
    assert nout.value == 1
    buf = ctypes.create_string_buffer(ref.nbytes)
    native.capi_check(lib.MXNDArraySyncCopyToCPU(
        ctypes.c_void_p(outs[0]), buf, ctypes.c_uint64(ref.nbytes)))
    np.testing.assert_allclose(
        np.frombuffer(buf.raw, np.float32).reshape(ref.shape), ref,
        rtol=1e-6)
    for hh in (ctypes.c_void_p(outs[0]), h):
        native.capi_check(lib.MXNDArrayFree(hh))
    native.capi_check(lib.MXDeployFree(served))
