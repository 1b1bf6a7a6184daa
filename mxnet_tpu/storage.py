"""Storage / device-memory introspection and allocator knobs.

TPU-native counterpart of the reference's storage manager surface
(ref: src/storage/** pooled_storage_manager + MXNET_GPU_MEM_POOL_* env
knobs + mx.context.gpu_memory_info).  Allocation itself belongs to
PjRt/XLA by design (SURVEY.md N3: "delegate to PjRt, expose the
introspection"); this module exposes what a user needs when a model
OOMs:

  * memory_info(ctx)     -> (free_bytes, total_bytes) like the
    reference's gpu_memory_info, from the device's PjRt allocator stats.
  * memory_summary(ctx)  -> allocator stats + FRAMEWORK-side live-buffer
    accounting (count/bytes of live jax arrays per device) that works
    even on PJRT plugins that do not report allocator stats (the v5e's
    libtpu does report them: bytes_in_use, peak_bytes_in_use,
    bytes_limit).
  * configure(...)       -> the reference's pool knobs mapped onto XLA's
    client options (must run before backend init, like the reference's
    env-var contract):
        pool_reserve_pct  <- MXNET_GPU_MEM_POOL_RESERVE
        preallocate       <- (XLA_PYTHON_CLIENT_PREALLOCATE)
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

from .base import MXNetError
from .util import env

__all__ = ["memory_info", "memory_summary", "memory_summaries",
           "configure", "live_array_bytes"]


def _device_of(ctx=None):
    import jax

    from .context import Context, current_context

    ctx = ctx or current_context()
    if isinstance(ctx, Context):
        return ctx.jax_device
    return ctx  # already a jax device


def live_array_bytes(ctx=None) -> Tuple[int, int]:
    """(n_live_arrays, total_bytes) of framework-visible live buffers on
    the device — allocator-independent accounting."""
    import jax

    dev = _device_of(ctx)
    n = total = 0
    for a in jax.live_arrays():
        try:
            if dev in a.devices():
                n += 1
                total += a.nbytes // max(1, len(a.devices()))
        except Exception:  # deleted/donated buffers
            continue
    return n, total


def memory_summaries(devices=None) -> Dict[object, Tuple[int, int]]:
    """Live-buffer accounting for MANY devices in ONE pass over
    ``jax.live_arrays()`` -> {device: (n_live, total_bytes)}.  The
    per-device :func:`live_array_bytes` rescans the whole live set per
    call; telemetry's HBM sampling (mxprof) wants every local device
    at once, so this amortizes the scan."""
    import jax

    devs = list(devices) if devices is not None else jax.local_devices()
    acc: Dict[object, list] = {d: [0, 0] for d in devs}
    for a in jax.live_arrays():
        try:
            adevs = a.devices()
            share = a.nbytes // max(1, len(adevs))
            for d in adevs:
                slot = acc.get(d)
                if slot is not None:
                    slot[0] += 1
                    slot[1] += share
        except Exception:  # deleted/donated buffers
            continue
    return {d: (n, total) for d, (n, total) in acc.items()}


def memory_info(ctx=None) -> Tuple[int, int]:
    """(free_bytes, total_bytes) for the device
    (ref: mx.context.gpu_memory_info -> cudaMemGetInfo).  Raises
    MXNetError when the PJRT plugin does not report allocator stats —
    with the live-buffer fallback mentioned in the message."""
    dev = _device_of(ctx)
    stats = dev.memory_stats()
    if not stats:
        n, used = live_array_bytes(ctx)
        raise MXNetError(
            f"device {dev} does not report allocator stats "
            f"(PJRT plugin limitation); framework-side live buffers: "
            f"{n} arrays / {used} bytes — see storage.memory_summary")
    total = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    in_use = stats.get("bytes_in_use", 0)
    if total is None:
        total = stats.get("peak_bytes_in_use", in_use)
    return int(total) - int(in_use), int(total)


def memory_summary(ctx=None) -> Dict[str, object]:
    """Full introspection dict: PjRt allocator stats (when available) +
    live-buffer accounting (always)."""
    dev = _device_of(ctx)
    try:
        stats = dev.memory_stats() or {}
    except Exception:
        stats = {}
    n, used = live_array_bytes(ctx)
    return {
        "device": str(dev),
        "platform": dev.platform,
        "allocator_stats": dict(stats),
        "live_arrays": n,
        "live_array_bytes": used,
    }


def configure(pool_reserve_pct: Optional[int] = None,
              preallocate: Optional[bool] = None) -> None:
    """Set allocator knobs (must run BEFORE the jax backend initializes,
    the same contract as the reference's MXNET_GPU_MEM_POOL_* env vars).

    pool_reserve_pct: percent of device memory to keep OUT of the pool
        (ref: MXNET_GPU_MEM_POOL_RESERVE) -> XLA client mem fraction.
    preallocate: grab the pool up front vs grow on demand.
    """
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise MXNetError(
            "storage.configure must be called before the first jax "
            "backend use (same before-init contract as the reference's "
            "MXNET_GPU_MEM_POOL_* variables)")
    if pool_reserve_pct is not None:
        if not 0 <= pool_reserve_pct < 100:
            raise MXNetError("pool_reserve_pct must be in [0, 100)")
        os.environ["XLA_PYTHON_CLIENT_MEM_FRACTION"] = str(
            (100 - pool_reserve_pct) / 100.0)
    if preallocate is not None:
        os.environ["XLA_PYTHON_CLIENT_PREALLOCATE"] = \
            "true" if preallocate else "false"


def _env_pool_reserve_default() -> None:
    """Honor the reference env var spelling at import."""
    reserve = env.get_int("MXNET_GPU_MEM_POOL_RESERVE")
    if reserve is not None and \
            "XLA_PYTHON_CLIENT_MEM_FRACTION" not in os.environ:
        os.environ["XLA_PYTHON_CLIENT_MEM_FRACTION"] = str(
            (100 - reserve) / 100.0)


_env_pool_reserve_default()
