"""Executable cost accounting: FLOPs/bytes per compiled program.

``compiled.cost_analysis()`` is XLA's own static cost model for a
compiled executable — FLOPs and bytes accessed.  It is captured ONCE
per executable at the compile-cache sites (fused step, SPMD step, the
gspmd whole-step trainer, serving buckets) and stored next to the
cached executable, so a program that came back from the persistent
compile cache keeps its cost metadata the same as a fresh build: the
analysis runs on the loaded executable object, not on the build.

Combined with step wall time (the flight recorder) this yields
``mx_step_mfu`` and the per-step roofline verdict.  The MFU
denominator is the per-device peak FLOP/s: ``MXNET_PEAK_FLOPS``
overrides; otherwise the device-kind table below answers for known
TPU generations, and an unknown device reports MFU as None — a
made-up utilization is worse than none.
"""
from __future__ import annotations

import threading
from typing import Dict, NamedTuple, Optional, Tuple

from ...util import env as _env

__all__ = ["Cost", "executable_cost", "peak_flops",
           "backend_initialized", "note", "notes", "hlo_fingerprint"]


class Cost(NamedTuple):
    flops: float
    bytes_accessed: float


def executable_cost(compiled) -> Optional[Cost]:
    """Cost of one compiled executable, or None when the backend (or a
    deserialized payload) does not support cost analysis.  Never
    raises — attribution must not break a compile."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend/payload may not support it
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None
    try:
        flops = float(ca.get("flops", 0.0) or 0.0)
        nbytes = float(ca.get("bytes accessed",
                              ca.get("bytes_accessed", 0.0)) or 0.0)
    except (TypeError, ValueError):
        return None
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return Cost(flops, nbytes)


# peak dense FLOP/s per chip by device-kind substring (bf16 MXU peak,
# public TPU specs); matched case-insensitively, first hit wins.  CPU
# and unknown accelerators resolve to None.  "v5 lite" is the
# device_kind the v5e reports under jax 0.9.0 / libtpu 0.0.34
# ("TPU v5 lite", read on the chip in PR 21); the "v5e"/"v5litepod"
# spellings stay for runtimes that name it differently.
_PEAK_BY_KIND: Tuple[Tuple[str, float], ...] = (
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5litepod", 197e12),
    ("v6e", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def backend_initialized() -> bool:
    """Whether a jax backend is up — an 'unknown' peak answered while
    the backend is still down is provisional (the device kind could
    not be read yet), not final."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def peak_flops(device_kind: Optional[str] = None
               ) -> Tuple[Optional[float], str]:
    """(per-device peak FLOP/s, source) — source is ``env`` / ``table``
    / ``unknown``.  ``device_kind`` defaults to the first visible
    device's kind (resolved lazily; never initializes a backend that
    is not already up)."""
    v = _env.get_float("MXNET_PEAK_FLOPS")
    if v:
        return float(v), "env"
    if device_kind is None:
        if not backend_initialized():
            return None, "unknown"
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    for sub, peak in _PEAK_BY_KIND:
        if sub in kind:
            return peak, "table"
    return None, "unknown"


# ---- per-site cost notes (what dump() reports) ------------------------

_NOTES_MAX = 256
_notes_lock = threading.Lock()
_notes: Dict[str, Dict[str, dict]] = {}


def note(site: str, key: str, cost: Optional[Cost],
         fingerprint: Optional[str] = None) -> None:
    """Remember one executable's cost (and, when known, its HLO-module
    fingerprint) under (site, key) for dumps — bounded per site so
    long-lived processes stay flat.  The fingerprint rides beside the
    cost so perf attribution can say "the compiled program did (not)
    change" across runs."""
    if cost is None and fingerprint is None:
        return
    with _notes_lock:
        per = _notes.setdefault(site, {})
        if key not in per and len(per) >= _NOTES_MAX:
            per.pop(next(iter(per)))
        row = {}
        if cost is not None:
            row = {"flops": cost.flops,
                   "bytes_accessed": cost.bytes_accessed}
        if fingerprint is not None:
            row["hlo_fingerprint"] = fingerprint
        per[key] = row


def hlo_fingerprint(compiled, program_text: Optional[str] = None
                    ) -> Optional[str]:
    """sha256 identity of one executable's HLO module: the lowered
    program text when the caller has it (free — it was rendered for
    the cache key), else the compiled module's own text, else None
    (deserialized payloads may not render)."""
    import hashlib

    text = program_text
    if text is None:
        try:
            text = compiled.as_text()
        except Exception:  # noqa: BLE001 — best effort on loaded payloads
            return None
    if not text:
        return None
    return hashlib.sha256(text.encode()).hexdigest()


def notes() -> Dict[str, Dict[str, dict]]:
    with _notes_lock:
        return {s: dict(d) for s, d in _notes.items()}
