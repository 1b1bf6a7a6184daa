"""What the zoo's decoder stacks share (`nemotron_h.py`, `laguna.py`):
the pre-norm residual sub-layer, the bias-free projection, the final norm
and untied head, and the rule that named parameters keep float32 under
`cast`."""
from __future__ import annotations

from ..block import HybridBlock

FP32 = "float32"


def project(F, x, weight):
    return F.FullyConnected(x, weight, None, num_hidden=weight.shape[0],
                            no_bias=True, flatten=False)


def norm_residual(F, x, norm_weight, eps, mix, *args, **params):
    """x + mix(F, RMSNorm(x), ...).  Where `mix` gives (output,
    statistics...) the statistics pass through beside the sum."""
    mixed = mix(F, F.RMSNorm(x, norm_weight, eps=eps), *args, **params)
    if isinstance(mixed, (list, tuple)):
        return (x + mixed[0], *mixed[1:])
    return x + mixed


class Layer(HybridBlock):
    """h + mixer(RMSNorm(h)); subclasses give `mix`.  Parameters named in
    `_FLOAT32` keep float32 under `cast`, as the published model keeps
    them."""

    _FLOAT32 = ()

    def __init__(self, hidden_size, eps, **kwargs):
        super().__init__(**kwargs)
        self._hidden, self._eps = hidden_size, eps
        with self.name_scope():
            self.norm_weight = self.params.get(
                "norm_weight", shape=(hidden_size,), init="ones")

    def cast(self, dtype):
        self._clear_cached_op()
        for name, p in self._reg_params.items():
            p.cast(FP32 if name in self._FLOAT32 else dtype)

    def hybrid_forward(self, F, x, norm_weight, **params):
        return norm_residual(F, x, norm_weight, self._eps, self.mix,
                             **params)


class Head(HybridBlock):
    """Final RMSNorm and the untied vocabulary projection."""

    def __init__(self, hidden_size, vocab_size, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.norm_weight = self.params.get(
                "norm_weight", shape=(hidden_size,), init="ones")
            self.weight = self.params.get(
                "weight", shape=(vocab_size, hidden_size))

    def hybrid_forward(self, F, x, norm_weight, weight):
        return project(F, F.RMSNorm(x, norm_weight, eps=self._eps), weight)
