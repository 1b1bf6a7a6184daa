"""Layer: models.  Device time per step on chip 0, forward, the forward
done again under remat and backward, of every instruction of the step
program whose name stack passes through the looped stack's scope (`ut`:
`gluon.model_zoo.ouro.PASS_NAME`, the one traced pass that the scan's
loops run four times): the N layers' norms, projections, rotation,
causal cores and gated MLPs and the final norm, over the same parameters
every trip, and the loops' own carries and stacked residuals; not the
embedding, the exits or the update.  The four trips are one set of
instructions, so the trace cannot tell them apart.

`mtp_device_ms`'s reader (its docstring says how it reads) over another
name: a copy of that module loaded for this file alone, its pattern
rewritten from `mtp` to the scope here."""
import re

from harness import lookup

BLOCK = "ut"

_reader = lookup._module(lookup.BENCH_DIR, "layer_metrics",
                         "mtp_device_ms.py")
_reader._IN_BLOCK = re.compile(
    _reader._IN_BLOCK.pattern.replace(_reader.BLOCK, BLOCK))
read = _reader.read
