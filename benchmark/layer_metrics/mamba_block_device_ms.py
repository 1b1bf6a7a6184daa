"""Layer: models.  Device time per step on chip 0, forward and backward
(with the forward done again under remat), of every instruction of the
step program whose name stack passes through a Mamba mixer's scope
(`mamba`: `gluon.model_zoo.phi4flash.MAMBA_NAME`): the input projection
2560 -> 2 x 5120, the causal convolution and its SiLU, x_proj, dt_proj,
the `selective_scan` op, the gate and the output projection, in the
three Mamba layers; not the layer's norms, residual sums or MLP half.

`mtp_device_ms`'s reader (its docstring says how it reads) over another
name: a copy of that module loaded for this file alone, its pattern
rewritten from `mtp` to the scope here."""
import re

from harness import lookup

BLOCK = "mamba"

_reader = lookup._module(lookup.BENCH_DIR, "layer_metrics",
                         "mtp_device_ms.py")
_reader._IN_BLOCK = re.compile(
    _reader._IN_BLOCK.pattern.replace(_reader.BLOCK, BLOCK))
read = _reader.read
