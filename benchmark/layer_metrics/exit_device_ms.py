"""Layer: models.  Device time per step on chip 0, forward, the forward
done again under remat and backward, of every instruction of the step
program whose name stack passes through the exits' scope (`exit`:
`gluon.model_zoo.ouro.EXIT_NAME`): the four exits' products with the
head's array over the whole 49,152-row vocabulary, their log-softmax and
target's score, the gate's column, and the objective over the four
exits, which the step block traces under the same name; not the final
norm (a pass's) and not the update of the head's array where XLA runs
it apart (`ouro_optimizer_device_ms`).

`mtp_device_ms`'s reader (its docstring says how it reads) over another
name: a copy of that module loaded for this file alone, its pattern
rewritten from `mtp` to the scope here."""
import re

from harness import lookup

BLOCK = "exit"

_reader = lookup._module(lookup.BENCH_DIR, "layer_metrics",
                         "mtp_device_ms.py")
_reader._IN_BLOCK = re.compile(
    _reader._IN_BLOCK.pattern.replace(_reader.BLOCK, BLOCK))
read = _reader.read
