"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of every instruction of the step program whose name stack passes through
`differential_attention/window`: the op `differential_attention` in the
two layers under the sliding window of 512 (the grouped core at 64-wide
queries and keys and 128-wide values through the splash kernels over a
`LocalMask`, split backward, and the copies around them).

`mtp_device_ms`'s reader (its docstring says how it reads) over another
name: a copy of that module loaded for this file alone, its pattern
rewritten from `mtp` to the scope here."""
import re

from harness import lookup

# `jvp(` / `transpose(` close after the component they wrap
BLOCK = r"differential_attention\)*/window"

_reader = lookup._module(lookup.BENCH_DIR, "layer_metrics",
                         "mtp_device_ms.py")
_reader._IN_BLOCK = re.compile(
    _reader._IN_BLOCK.pattern.replace(_reader.BLOCK, BLOCK))
read = _reader.read
