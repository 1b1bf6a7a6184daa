"""shard_map as the parallel package calls it."""
from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map_unchecked(fn, *, mesh, in_specs, out_specs):
    """shard_map with varying-axis checking disabled — the body functions
    here mix replicated accumulators with axis-varying data, which
    check_vma rejects."""
    return _shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)
