"""Layer: kernels.  Device time per step on chip 0, forward and backward,
of every instruction of the step program whose name stack passes through
`differential_attention/full`: the op `differential_attention` in the
one full layer and the cross layer (the grouped causal core at 64-wide
queries and keys and 128-wide values, 40 heads over 20, S = 16384: the
splash forward kernel and `mx_causal_attention_bwd`, and the head-split
and pairing copies around them), not lambda, the sub-norm or the
projections, which the op traces outside `full`.

`mtp_device_ms`'s reader (its docstring says how it reads) over another
name: a copy of that module loaded for this file alone, its pattern
rewritten from `mtp` to the scope here."""
import re

from harness import lookup

# `jvp(` / `transpose(` close after the component they wrap
BLOCK = r"differential_attention\)*/full"

_reader = lookup._module(lookup.BENCH_DIR, "layer_metrics",
                         "mtp_device_ms.py")
_reader._IN_BLOCK = re.compile(
    _reader._IN_BLOCK.pattern.replace(_reader.BLOCK, BLOCK))
read = _reader.read
