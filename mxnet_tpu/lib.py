"""Native library loader: builds (if needed) and binds src/ via ctypes.

Counterpart of the reference's `python/mxnet/base.py` `_LIB` loader +
`check_call` over the flat C ABI (ref: include/mxnet/c_api.h; the
reference also binds exclusively through ctypes — no pybind11).

The library is built on demand from `src/*.cc` (g++ direct; the canonical
CMake build in src/CMakeLists.txt produces the same .so) and cached in
`build/`.  Everything degrades gracefully: `available()` is False when no
toolchain exists, and pure-Python paths take over.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
import weakref
from typing import List, Optional

from .base import MXNetError
from .util import env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO, "src")
_BUILD = os.path.join(_REPO, "build")
_SO = os.path.join(_BUILD, "libmxnet_tpu_native.so")

_lock = threading.Lock()

EngineFnType = ctypes.CFUNCTYPE(None, ctypes.c_void_p)

# image_pipeline.cc links OpenCV and builds into its own .so (see below);
# ndarray_capi.cc links libpython and builds into its own .so too —
# the core library must stay dependency-free
_CORE_EXCLUDE = {"image_pipeline.cc", "ndarray_capi.cc"}


def _sources() -> List[str]:
    return sorted(
        os.path.join(_SRC, f) for f in os.listdir(_SRC)
        if f.endswith(".cc") and f not in _CORE_EXCLUDE)


def _img_sources() -> List[str]:
    return [os.path.join(_SRC, "image_pipeline.cc"),
            os.path.join(_SRC, "engine.cc")]


class _NativeLib:
    """One build-on-demand ctypes library: source-hash staleness check, g++
    fallback build, env gate, double-checked-lock load, error ring."""

    def __init__(self, so_name: str, sources_fn, extra_flags: List[str],
                 err_sym: str, what: str):
        self.so_path = os.path.join(_BUILD, so_name)
        self._sources_fn = sources_fn
        self._flags = extra_flags
        self._err_sym = err_sym
        self._what = what
        self._lib: Optional[ctypes.CDLL] = None
        self._tried = False

    def _fingerprint(self) -> str:
        """sha256 over the flags and every source/header's CONTENT: a
        fresh copy or checkout scrambles mtimes, bytes it cannot."""
        deps = self._sources_fn() + sorted(
            os.path.join(_SRC, f) for f in os.listdir(_SRC)
            if f.endswith(".h"))
        h = hashlib.sha256(" ".join(self._flags).encode())
        for p in deps:
            with open(p, "rb") as f:
                h.update(f.read())
        return h.hexdigest()

    def _needs_build(self) -> bool:
        stamp = self.so_path + ".src"
        if not (os.path.exists(self.so_path) and os.path.exists(stamp)):
            return True
        with open(stamp) as f:
            return f.read() != self._fingerprint()

    def _build(self) -> None:
        os.makedirs(_BUILD, exist_ok=True)
        # build beside the target and rename: a concurrent process never
        # dlopens a half-written library
        tmp = f"{self.so_path}.{os.getpid()}.tmp"
        cmd = (["g++", "-std=c++17", "-O2", "-shared", "-fPIC", "-pthread",
                "-Wall", "-o", tmp] + self._sources_fn() +
               self._flags)
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise MXNetError(f"{self._what} build failed:\n"
                             f"{' '.join(cmd)}\n{proc.stderr[-4000:]}")
        os.replace(tmp, self.so_path)
        with open(self.so_path + ".src", "w") as f:
            f.write(self._fingerprint())

    def load(self) -> Optional[ctypes.CDLL]:
        if self._lib is not None or self._tried:
            return self._lib
        with _lock:
            if self._lib is not None or self._tried:
                return self._lib
            self._tried = True
            if not env.get_bool("MXNET_USE_NATIVE"):
                return None
            try:
                if self._needs_build():
                    self._build()
                lib = ctypes.CDLL(self.so_path)
            except Exception:
                return None
            getattr(lib, self._err_sym).restype = ctypes.c_char_p
            self._lib = lib
        return self._lib

    def check(self, ret: int) -> None:
        if ret != 0:
            raise MXNetError(getattr(self._lib, self._err_sym)()
                             .decode("utf-8", "replace"))


def _capi_sources() -> List[str]:
    return [os.path.join(_SRC, "ndarray_capi.cc")]


def _capi_flags() -> List[str]:
    """Python embedding flags from sysconfig (no python3-config needed)."""
    import sysconfig

    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") or \
        f"{sys.version_info.major}.{sys.version_info.minor}"
    flags = [f"-I{inc}"]
    if libdir:
        flags += [f"-L{libdir}", f"-Wl,-rpath,{libdir}"]
    flags += [f"-lpython{ver}"]
    return flags


_CORE = _NativeLib("libmxnet_tpu_native.so", _sources, [],
                   "MXGetLastError", "native")
_IMAGE = _NativeLib("libmxnet_tpu_image.so", _img_sources,
                    ["-I/usr/include/opencv4", "-lopencv_core",
                     "-lopencv_imgproc", "-lopencv_imgcodecs"],
                    "MXImageGetLastError", "image pipeline")
_CAPI = _NativeLib("libmxnet_tpu_capi.so", _capi_sources, _capi_flags(),
                   "MXCapiGetLastError", "ndarray c-api")


def _load() -> Optional[ctypes.CDLL]:
    return _CORE.load()


def available() -> bool:
    return _CORE.load() is not None


def get() -> ctypes.CDLL:
    lib = _CORE.load()
    if lib is None:
        raise MXNetError(
            "native library unavailable (no toolchain or build failed); "
            "set MXNET_USE_NATIVE=0 to silence native paths entirely")
    return lib


def check_call(ret: int) -> None:
    """ref: base.py::check_call — raise MXNetError from the error ring."""
    if ret != 0:
        raise MXNetError(get().MXGetLastError().decode("utf-8", "replace"))


# ---------------------------------------------------------------------------
# Engine wrapper (ref: Engine::PushAsync contract, SURVEY.md CS1 async
# boundary — here scheduling HOST-side work; device work rides PjRt)
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Fork safety (ref role: src/initialize.cc pthread_atfork handlers —
# quiesce engine threads before fork; don't let the child inherit handles
# whose worker threads/mutexes did not survive the fork)
# ---------------------------------------------------------------------------

_FORK_REGISTRY: "weakref.WeakSet" = weakref.WeakSet()
_FORK_HOOKS_INSTALLED = False


def _register_fork_guard(obj) -> None:
    _FORK_REGISTRY.add(obj)


def _before_fork() -> None:
    for obj in list(_FORK_REGISTRY):
        try:
            obj._quiesce_before_fork()
        except Exception:
            pass


def _after_fork_child() -> None:
    for obj in list(_FORK_REGISTRY):
        try:
            obj._after_fork_child()
        except Exception:
            pass


def _after_fork_parent() -> None:
    for obj in list(_FORK_REGISTRY):
        try:
            obj._after_fork_parent()
        except Exception:
            pass


def install_fork_handlers() -> None:
    """Register atfork hooks (idempotent; runs on os.fork / the
    multiprocessing 'fork' start method, NOT on subprocess spawn).
    Does not load the native library."""
    global _FORK_HOOKS_INSTALLED
    if _FORK_HOOKS_INSTALLED or not hasattr(os, "register_at_fork"):
        return
    _FORK_HOOKS_INSTALLED = True
    os.register_at_fork(before=_before_fork,
                        after_in_parent=_after_fork_parent,
                        after_in_child=_after_fork_child)


class _HandleGuard:
    """Mixin: `_hh()` returns the live native handle or raises loudly —
    a closed or fork-invalidated handle must never reach C++ as NULL."""

    _fork_invalid = False

    def _hh(self) -> ctypes.c_void_p:
        h = getattr(self, "_h", None)
        if not h:
            why = ("invalidated by fork (native threads/file offsets do "
                   "not survive into the child; recreate the object)"
                   if self._fork_invalid else "already closed")
            raise MXNetError(
                f"{type(self).__name__}: native handle {why}")
        return h

    def _quiesce_before_fork(self) -> None:  # overridden where needed
        pass

    def _after_fork_parent(self) -> None:  # overridden where needed
        pass

    def _after_fork_child(self) -> None:
        # leak the C++ object on purpose: freeing it in the child would
        # join worker threads that only exist in the parent
        self._h = None
        self._fork_invalid = True


class NativeEngine(_HandleGuard):
    """Dependency-scheduled host task engine.

    `push(fn, read=[v1], write=[v2])` runs `fn()` on a worker thread once
    all hazards on the named variables clear; reads run concurrently,
    writes are exclusive and FIFO — the reference ThreadedEngine contract.
    `num_workers=0` gives the synchronous NaiveEngine (debug mode).
    """

    def __init__(self, num_workers: Optional[int] = None):
        if num_workers is None:
            if env.get_str("MXNET_ENGINE_TYPE") == "NaiveEngine":
                num_workers = 0
            else:
                num_workers = env.get_int(
                    "MXNET_CPU_WORKER_NTHREADS",
                    default=max(2, (os.cpu_count() or 2)))
        self._lib = get()
        h = ctypes.c_void_p()
        check_call(self._lib.MXEngineCreate(ctypes.c_int(num_workers),
                                            ctypes.byref(h)))
        self._h = h
        self.num_workers = num_workers
        # keep callback objects alive until executed
        self._cb_lock = threading.Lock()
        self._cbs = {}
        self._next_id = 1  # never 0: ctypes maps a NULL void* to None

        def _trampoline(arg):
            key = int(arg or 0)
            with self._cb_lock:
                fn = self._cbs.pop(key)
            try:
                fn()
            except Exception:  # worker threads must never unwind into C++
                import traceback

                traceback.print_exc()

        self._tramp = EngineFnType(_trampoline)
        _register_fork_guard(self)

    def _quiesce_before_fork(self) -> None:
        # drain all pending work so no worker thread holds an engine
        # mutex at the instant of fork (the child inherits the mutexes
        # but not the threads — a held lock would deadlock it forever),
        # then take the Python-side callback lock across the fork so the
        # child cannot inherit it mid-acquire (standard atfork protocol)
        if self._h:
            self.wait_for_all()
        self._cb_lock.acquire()
        self._cb_lock_held_for_fork = True

    def _after_fork_parent(self) -> None:
        # only release what _quiesce_before_fork actually took: a bare
        # release() could strip the lock from a thread inside push()
        # when the quiesce raised before acquiring
        if getattr(self, "_cb_lock_held_for_fork", False):
            self._cb_lock_held_for_fork = False
            try:
                self._cb_lock.release()
            except RuntimeError:
                pass

    def _after_fork_child(self) -> None:
        # the parent's worker threads don't exist here; leak the old C++
        # engine (freeing would join ghost threads) and mark for LAZY
        # rebuild — a child that never touches the engine pays nothing
        # (the reference likewise restarts its engine lazily after fork,
        # src/initialize.cc role).  Pre-fork variable ids belong to the
        # leaked engine and error loudly on the rebuilt one.
        self._h = None
        self._needs_rebuild = True
        self._cb_lock = threading.Lock()  # fresh, never inherited-held
        self._cb_lock_held_for_fork = False

    def _hh(self) -> ctypes.c_void_p:
        if getattr(self, "_needs_rebuild", False):
            self._needs_rebuild = False
            h = ctypes.c_void_p()
            check_call(self._lib.MXEngineCreate(
                ctypes.c_int(self.num_workers), ctypes.byref(h)))
            self._h = h
            with self._cb_lock:
                self._cbs.clear()
        return super()._hh()

    def new_variable(self) -> int:
        v = ctypes.c_int64()
        check_call(self._lib.MXEngineNewVariable(self._hh(),
                                                 ctypes.byref(v)))
        return v.value

    def delete_variable(self, var: int) -> None:
        check_call(self._lib.MXEngineDeleteVariable(self._hh(),
                                                    ctypes.c_int64(var)))

    def push(self, fn, read=(), write=(), priority: int = 0) -> None:
        # convert BEFORE stashing: a bad var id must not leak the
        # callback into _cbs
        rv = (ctypes.c_int64 * len(read))(*read)
        wv = (ctypes.c_int64 * len(write))(*write)
        with self._cb_lock:
            key = self._next_id
            self._next_id += 1
            self._cbs[key] = fn
        try:
            check_call(self._lib.MXEnginePushAsync(
                self._hh(), self._tramp, ctypes.c_void_p(key), rv,
                len(read), wv, len(write), ctypes.c_int(priority)))
        except BaseException:
            # rejected push (duplicate-var check, dead handle): the
            # trampoline will never pop the stash — do it here or the
            # callable (and its closure) leaks on every retry
            with self._cb_lock:
                self._cbs.pop(key, None)
            raise

    def wait_for_var(self, var: int) -> None:
        check_call(self._lib.MXEngineWaitForVar(self._hh(),
                                                ctypes.c_int64(var)))

    def wait_for_all(self) -> None:
        check_call(self._lib.MXEngineWaitForAll(self._hh()))

    def num_pending(self) -> int:
        out = ctypes.c_int()
        check_call(self._lib.MXEngineNumPending(self._hh(),
                                                ctypes.byref(out)))
        return out.value

    def var_version(self, var: int) -> int:
        out = ctypes.c_uint64()
        check_call(self._lib.MXEngineVarVersion(self._hh(),
                                                ctypes.c_int64(var),
                                                ctypes.byref(out)))
        return out.value

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.MXEngineFree(self._h)
                self._h = None
        except Exception:
            pass


# ---------------------------------------------------------------------------
# RecordIO wrappers (native fast path for mxnet_tpu/recordio.py)
# ---------------------------------------------------------------------------

class NativeRecordWriter(_HandleGuard):
    def __init__(self, path: str, max_chunk: int = 0):
        # max_chunk=0 → the 29-bit wire default; smaller values exercise
        # the cflag-chained chunk path without gigabyte fixtures
        self._lib = get()
        h = ctypes.c_void_p()
        if max_chunk:
            check_call(self._lib.MXRecordIOWriterCreateEx(
                path.encode(), ctypes.c_size_t(max_chunk), ctypes.byref(h)))
        else:
            check_call(self._lib.MXRecordIOWriterCreate(
                path.encode(), ctypes.byref(h)))
        self._h = h
        _register_fork_guard(self)

    def write(self, buf: bytes) -> int:
        pos = ctypes.c_int64()
        check_call(self._lib.MXRecordIOWriterWrite(
            self._hh(), buf, ctypes.c_size_t(len(buf)), ctypes.byref(pos)))
        return pos.value

    def close(self):
        if self._h:
            check_call(self._lib.MXRecordIOWriterFree(self._h))
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class _ReaderBase(_HandleGuard):
    _create = _next = _reset = _free = None  # bound by subclass

    def __init__(self, path: str, *extra):
        self._lib = get()
        h = ctypes.c_void_p()
        check_call(self._create(path.encode(), *extra, ctypes.byref(h)))
        self._h = h
        _register_fork_guard(self)

    def read(self) -> Optional[bytes]:
        buf = ctypes.c_char_p()
        length = ctypes.c_size_t()
        eof = ctypes.c_int()
        check_call(self._next(self._hh(), ctypes.byref(buf),
                              ctypes.byref(length), ctypes.byref(eof)))
        if eof.value:
            return None
        return ctypes.string_at(buf, length.value)

    def reset(self):
        check_call(self._reset(self._hh()))

    def close(self):
        if self._h:
            check_call(self._free(self._h))
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeRecordReader(_ReaderBase):
    def __init__(self, path: str):
        lib = get()
        self._create = lib.MXRecordIOReaderCreate
        self._next = lib.MXRecordIOReaderNext
        self._reset = lib.MXRecordIOReaderReset
        self._free = lib.MXRecordIOReaderFree
        super().__init__(path)

    def seek(self, pos: int):
        check_call(self._lib.MXRecordIOReaderSeek(self._hh(),
                                                  ctypes.c_int64(pos)))


class NativePrefetchReader(_ReaderBase):
    """Background-thread prefetching record reader (dmlc ThreadedIter)."""

    def __init__(self, path: str, capacity: int = 64):
        lib = get()
        self._create = lib.MXPrefetchReaderCreate
        self._next = lib.MXPrefetchReaderNext
        self._reset = lib.MXPrefetchReaderReset
        self._free = lib.MXPrefetchReaderFree
        super().__init__(path, ctypes.c_int(capacity))


# ---------------------------------------------------------------------------
# Image pipeline (src/image_pipeline.cc, separate .so: links OpenCV like the
# reference's image pipeline; absence degrades to the Python decode path)
# ---------------------------------------------------------------------------

def capi_available() -> bool:
    """The NDArray/op C ABI .so (src/ndarray_capi.cc) builds and loads.

    RTLD_GLOBAL load path is in capi_get(): the library references
    libpython symbols which, inside a Python process, resolve from the
    interpreter already mapped into the process; standalone consumers
    link -lpython explicitly."""
    return _CAPI.load() is not None


def capi_get() -> ctypes.CDLL:
    lib = _CAPI.load()
    if lib is None:
        raise MXNetError("ndarray c-api library unavailable "
                         "(no toolchain or build failed)")
    return lib


def capi_check(ret: int) -> None:
    _CAPI.check(ret)


def image_available() -> bool:
    return _IMAGE.load() is not None


def _load_image() -> Optional[ctypes.CDLL]:
    return _IMAGE.load()


def _img_check(lib, ret: int) -> None:
    _IMAGE.check(ret)


class NativeImagePipeline(_HandleGuard):
    """Threaded decode+augment+batch pipeline over a .rec shard
    (src/image_pipeline.cc; decode tasks run on the N1 engine)."""

    def __init__(self, rec_path: str, idx_path: Optional[str], **cfg):
        import numpy as np

        self._np = np
        self._lib = _load_image()
        if self._lib is None:
            raise MXNetError("native image pipeline unavailable "
                             "(OpenCV toolchain missing?)")
        self.cfg = cfg
        cfg_s = ";".join(f"{k}={int(v) if isinstance(v, bool) else v}"
                         for k, v in cfg.items())
        h = ctypes.c_void_p()
        _img_check(self._lib, self._lib.MXImagePipelineCreate(
            rec_path.encode(), idx_path.encode() if idx_path else None,
            cfg_s.encode(), ctypes.byref(h)))
        self._h = h
        _register_fork_guard(self)

    def next(self):
        """-> (data ndarray, label ndarray, pad) or None at epoch end.
        data is u8 NHWC (default) or f32 NCHW (normalize=1)."""
        np = self._np
        batch_h = ctypes.c_void_p()
        data_p = ctypes.POINTER(ctypes.c_uint8)()
        label_p = ctypes.POINTER(ctypes.c_float)()
        pad = ctypes.c_int()
        _img_check(self._lib, self._lib.MXImagePipelineNext(
            self._hh(), ctypes.byref(batch_h), ctypes.byref(data_p),
            ctypes.byref(label_p), ctypes.byref(pad)))
        if not batch_h.value:
            return None
        b = int(self.cfg.get("batch", 1))
        c = int(self.cfg.get("channels", 3))
        hh = int(self.cfg.get("height", 224))
        ww = int(self.cfg.get("width", 224))
        lw = int(self.cfg.get("label_width", 1))
        norm = bool(self.cfg.get("normalize", False))
        n_el = b * c * hh * ww
        if norm:
            fp = ctypes.cast(data_p, ctypes.POINTER(ctypes.c_float))
            data = np.ctypeslib.as_array(fp, (n_el,)).reshape(
                b, c, hh, ww).copy()
        else:
            data = np.ctypeslib.as_array(data_p, (n_el,)).reshape(
                b, hh, ww, c).copy()
        label = np.ctypeslib.as_array(label_p, (b * lw,)).reshape(
            b, lw).copy()
        _img_check(self._lib,
                   self._lib.MXImagePipelineReleaseBatch(batch_h))
        return data, label, pad.value

    def reset(self):
        _img_check(self._lib, self._lib.MXImagePipelineReset(self._hh()))

    def close(self):
        if getattr(self, "_h", None):
            self._lib.MXImagePipelineFree(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
