"""Device contexts: cpu / tpu, with a thread-local `with ctx:` stack.

TPU-native counterpart of the reference's Context
(ref: include/mxnet/base.h Context{dev_type, dev_id};
python/mxnet/context.py Context/cpu()/gpu()/current_context()).

Here a Context maps onto a JAX device: ``tpu(i)`` is
``jax.devices('tpu')[i]``; ``cpu()`` is the host backend.  ``gpu(i)`` is
accepted for script compatibility and resolves to the accelerator backend
if one exists (so reference scripts with ``ctx=mx.gpu()`` run unmodified
on a TPU host).
"""
from __future__ import annotations

import os
import threading
from typing import Optional

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_tpus", "num_gpus"]


class Context:
    """A device context. devtype in {'cpu', 'tpu', 'gpu', 'cpu_pinned', 'cpu_shared'}."""

    # numeric ids kept stable with the reference's DeviceType enum where they
    # exist (kCPU=1, kGPU=2, kCPUPinned=3, kCPUShared=5); kTPU is new (=6).
    devtype2mask = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devmask2type = {v: k for k, v in devtype2mask.items()}

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devtype2mask:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)
        self._old_ctx: Optional["Context"] = None

    @property
    def device_typeid(self) -> int:
        return self.devtype2mask[self.device_type]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # --- with-stack (ref: python/mxnet/context.py __enter__/__exit__) ---
    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = self._old_ctx
        return False

    # --- JAX mapping -------------------------------------------------
    @property
    def jax_device(self):
        """Resolve to a concrete jax.Device (lazy; import jax here)."""
        import jax

        # device ids are PER-PROCESS (local): in a multi-process (DCN) job
        # each worker addresses only its own devices — ctx cpu(0)/tpu(0)
        # must never resolve to another process's buffer space
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _cpu_devices()
        else:
            devs = _accelerator_devices()
            if not devs:
                raise MXNetError(
                    f"context {self} requested but no accelerator devices present")
        if self.device_id >= len(devs):
            raise MXNetError(
                f"context {self}: device_id out of range ({len(devs)} present)")
        return devs[self.device_id]

    def empty_cache(self):
        """Reference API parity (Context.empty_cache). XLA manages HBM; no-op."""


def _accelerator_devices():
    """Local non-CPU devices.  A backend that fails to initialise (the
    chip held by another process, say) raises here: turning that into
    "no accelerator" would silently move the default context to cpu(0)."""
    import jax

    return [d for d in jax.local_devices() if d.platform != "cpu"]


def _cpu_devices():
    """The host backend's local devices.  Every model builder in the repo
    initialises on cpu() and then places onto the mesh, so a process
    whose JAX_PLATFORMS hides the CPU backend cannot run them."""
    import jax

    try:
        return jax.local_devices(backend="cpu")
    except RuntimeError as e:
        raise MXNetError(
            "context cpu() needs JAX's CPU backend next to the accelerator, "
            f"and it is not available (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}): list cpu there, e.g. "
            "JAX_PLATFORMS=tpu,cpu, or leave it unset") from e


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compat alias: resolves to the accelerator backend (TPU here)."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id: int = 0) -> Context:
    return Context("cpu_shared", device_id)


def num_tpus() -> int:
    return len(_accelerator_devices())


def num_gpus() -> int:
    """Compat: reference scripts probe mx.context.num_gpus()."""
    return len(_accelerator_devices())


def current_context() -> Context:
    """Thread-local current context; defaults to tpu(0) if present else cpu(0).

    The reference defaults to cpu(0); on a TPU host the accelerator is the
    natural default and reference scripts pass ctx explicitly anyway.
    Override with env MXNET_DEFAULT_CONTEXT=cpu|tpu.
    """
    cur = getattr(Context._default_ctx, "value", None)
    if cur is not None:
        return cur
    from .util import env

    forced = env.get_str("MXNET_DEFAULT_CONTEXT")
    if forced:
        return Context(forced, 0)
    return tpu(0) if num_tpus() > 0 else cpu(0)
