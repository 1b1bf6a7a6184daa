"""The last line of stdout: one JSON object with exactly the contracted
keys.  An extra key is a refusal (PR 21, cause (g)), so the line is built
here and nowhere else, and benchmark/tests pins it."""
from __future__ import annotations

import json

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = DEVICE_KEYS + ("busy_s", "window_s")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")


def line(*, correct, attempted, failed, metrics, device, breakdown=None):
    traced = "busy_s" in device
    want = TRACED_DEVICE_KEYS if traced else DEVICE_KEYS
    if tuple(device) != want:
        raise ValueError(f"device keys {tuple(device)}, contract {want}")
    for name, m in metrics.items():
        if tuple(m) != ("value", "unit"):
            raise ValueError(f"metric {name}: keys {tuple(m)}")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        if tuple(breakdown) != BREAKDOWN_KEYS or any(
                len(v) > 10 for v in breakdown.values()):
            raise ValueError(f"breakdown {breakdown}")
        out["breakdown"] = breakdown
    return json.dumps(out)
