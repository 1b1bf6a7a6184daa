"""Published peaks per chip, keyed by the `device_kind` JAX reports.

One table, one source line per entry.  A device that is not here is an
error, never a default: every utilisation the benchmark prints divides
by one of these.  (`mxnet_tpu/telemetry/mxprof/costs.py:_PEAK_BY_KIND`
is the program's own table and is not read by the benchmark.)
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    flops_bf16: float      # FLOP/s, bf16 on the MXU
    hbm_bytes_s: float     # bytes/s
    ici_bits_s: float      # bits/s, chip to chip
    hbm_bytes: float       # bytes of device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        flops_bf16=197e12, hbm_bytes_s=819e9, ici_bits_s=1600e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interchip '
               'interconnect'),
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} has no entry in "
            f"benchmark/harness/peaks.py (have {sorted(PEAKS)}): add its "
            "published peaks with their source before measuring on it"
        ) from None
