"""Layer: models.  Device time per step on chip 0, forward and backward
(with the forward done again under remat), of every instruction of the
step program whose name stack passes through a conv layer's operator
scope (`conv`: `gluon.model_zoo.lfm2.CONV_NAME`): the input projection
2048 -> 3 x 2048, the `short_conv` op and the output projection, in the
ten conv layers; not the layer's norms, residual sums or MLP half.  The
op has no metric of its own: XLA takes its forward pass into the output
projection's matmul, so only this scope holds all of its work.

`mtp_device_ms`'s reader (its docstring says how it reads) over another
name: a copy of that module loaded for this file alone, its pattern
rewritten from `mtp` to the scope here."""
import re

from harness import lookup

BLOCK = "conv"

_reader = lookup._module(lookup.BENCH_DIR, "layer_metrics",
                         "mtp_device_ms.py")
_reader._IN_BLOCK = re.compile(
    _reader._IN_BLOCK.pattern.replace(_reader.BLOCK, BLOCK))
read = _reader.read
