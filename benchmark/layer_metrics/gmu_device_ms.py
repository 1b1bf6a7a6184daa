"""Layer: models.  Device time per step on chip 0, forward and backward
(with the forward done again under remat), of every instruction of the
step program whose name stack passes through a Gated Memory Unit's
scope (`gmu`: `gluon.model_zoo.phi4flash.GMU_NAME`): the projection
2560 -> 5120, the SiLU gate on the memory m (another layer's scan
output, read as an argument of the segment) and the projection back; not
the layer's norms, residual sums or MLP half.

`mtp_device_ms`'s reader (its docstring says how it reads) over another
name: a copy of that module loaded for this file alone, its pattern
rewritten from `mtp` to the scope here."""
import re

from harness import lookup

BLOCK = "gmu"

_reader = lookup._module(lookup.BENCH_DIR, "layer_metrics",
                         "mtp_device_ms.py")
_reader._IN_BLOCK = re.compile(
    _reader._IN_BLOCK.pattern.replace(_reader.BLOCK, BLOCK))
read = _reader.read
