"""Runtime feature introspection (ref: python/mxnet/runtime.py over
src/libinfo.cc — `mx.runtime.feature_list()`, `Features`).

Build flags become runtime capability probes: TPU presence, native
extension availability, x64, etc.
"""
from __future__ import annotations

from collections import namedtuple
from typing import Dict, List

__all__ = ["Feature", "Features", "feature_list"]

Feature = namedtuple("Feature", ["name", "enabled"])


def _probe() -> Dict[str, bool]:
    feats: Dict[str, bool] = {}
    try:
        import jax

        feats["JAX"] = True
        try:
            platforms = {d.platform for d in jax.devices()}
        except Exception:
            platforms = set()
        feats["TPU"] = "tpu" in platforms
        feats["CPU"] = True
    except ImportError:  # pragma: no cover
        feats["JAX"] = feats["TPU"] = False
    feats["CUDA"] = False
    feats["CUDNN"] = False
    feats["NCCL"] = False
    feats["XLA_COLLECTIVES"] = feats.get("JAX", False)
    feats["BF16"] = feats.get("JAX", False)
    feats["INT8"] = feats.get("JAX", False)
    try:
        from . import lib  # native extension (C++ runtime layer)

        feats["NATIVE_ENGINE"] = lib.available()
    except Exception:
        feats["NATIVE_ENGINE"] = False
    feats["OPENCV"] = _has("cv2")
    feats["DIST_KVSTORE"] = True
    try:
        from .parallel import dist as _dist  # noqa: F401

        feats["DIST_KVSTORE"] = True
    except Exception:
        feats["DIST_KVSTORE"] = False
    feats["F16C"] = True
    return feats


def _has(mod: str) -> bool:
    import importlib.util

    return importlib.util.find_spec(mod) is not None


class Features(dict):
    """ref: runtime.Features — mapping name -> Feature."""

    def __init__(self):
        super().__init__([(k, Feature(k, v)) for k, v in _probe().items()])

    def __repr__(self):
        return f"[{', '.join(sorted(self.keys()))}]"

    def is_enabled(self, name: str) -> bool:
        feat = self.get(name.upper())
        return bool(feat and feat.enabled)


def feature_list() -> List[Feature]:
    """ref: runtime.feature_list."""
    return list(Features().values())
