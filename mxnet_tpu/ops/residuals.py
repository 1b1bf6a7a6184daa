"""What a recomputed segment keeps.

Under gradient mirroring (`SPMDTrainer(remat=True)`, `hybridize(mirror=
True)`) every parameter-bearing block is one `jax.checkpoint` segment
(gluon/block.py): the forward keeps its input, the backward runs its
forward again.  Some of what that forward computes only a kernel can
compute again, and the kernel had already written it to HBM: an
attention kernel's output and logsumexp, which its backward kernels
read.  Those values are NAMED here, inside the forward rule of
the kernel's custom VJP (`jax.ad_checkpoint.checkpoint_name`), and a
segment keeps the named values and recomputes everything else:
`KEEP_NAMED` is the policy of every segment.  A segment that names
nothing compiles to the program it compiled to without the policy, and
outside a segment a name is an identity.

One name a kernel route, the route's own (`pallas_attention.ROUTES`):
the XLA twins of those routes name nothing, since what they would keep
is the S x S (or S x 2W) score array.
"""
from __future__ import annotations

import contextlib
import threading

import jax

from ..telemetry import instruments as _instruments

__all__ = ["NAMES", "KEEP_NAMED", "kept_residuals"]

#: every name a forward rule may give a value; one place, one policy
NAMES = ("flash_causal", "splash_window", "eva_splash", "latent_splash",
         "diff_splash", "diff_window_splash")
KEEP_NAMED = jax.checkpoint_policies.save_only_these_names(*NAMES)

# Counted where a route that names its residuals is CHOSEN inside a
# segment (`kernel_route.choose`), from the shapes the kernel writes.  Not
# counted in the policy: `lax.cond`'s rule (every route sits in a
# `platform_dependent`) consults a policy twice an equation.  The
# telemetry counter `mx_remat_kept_bytes_total{name}` is the export.
_kept = {name: {"values": 0, "bytes": 0} for name in NAMES}
_lock = threading.Lock()
_tracing = threading.local()    # .depth: segments this thread is tracing


def kept_residuals():
    """{name: {"values", "bytes"}} of the named values that the segments
    traced since import keep.  Read it before and after to count."""
    with _lock:
        return {name: dict(kept) for name, kept in _kept.items()}


@contextlib.contextmanager
def segment():
    """Around the trace of one recomputed segment (gluon/block.py)."""
    _tracing.depth = getattr(_tracing, "depth", 0) + 1
    try:
        yield
    finally:
        _tracing.depth -= 1


def note(name, values, nbytes):
    """The route `name` was chosen, and its forward rule names `values`
    values of `nbytes` bytes together."""
    if name not in NAMES:
        raise ValueError(f"{name!r} is not a residual name: {NAMES}")
    if not getattr(_tracing, "depth", 0):
        return
    with _lock:
        _kept[name]["values"] += values
        _kept[name]["bytes"] += nbytes
    _instruments.remat_kept_bytes_total(name).inc(nbytes)
