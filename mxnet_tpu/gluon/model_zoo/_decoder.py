"""What the zoo's decoder stacks share (`nemotron_h.py`, `laguna.py`,
`evabyte.py`): the pre-norm residual sub-layer, the bias-free projection,
the gated MLP, the final norm and untied head, and the rule that named
parameters keep float32 under `cast`.  A norm's `offset` is added to its
stored gain (1: the unit offset, the gain stored from zero)."""
from __future__ import annotations

from ..block import HybridBlock

FP32 = "float32"


def project(F, x, weight):
    return F.FullyConnected(x, weight, None, num_hidden=weight.shape[0],
                            no_bias=True, flatten=False)


def gated_mlp(F, x, gate_weight, up_weight, down_weight):
    """(silu(x G) * (x U)) Dn."""
    return project(F, F.Activation(project(F, x, gate_weight),
                                   act_type="silu")
                   * project(F, x, up_weight), down_weight)


def norm_residual(F, x, norm_weight, eps, mix, *args, offset=0.0,
                  **params):
    """x + mix(F, RMSNorm(x), ...).  Where `mix` gives (output,
    statistics...) the statistics pass through beside the sum."""
    mixed = mix(F, F.RMSNorm(x, norm_weight, eps=eps, offset=offset),
                *args, **params)
    if isinstance(mixed, (list, tuple)):
        return (x + mixed[0], *mixed[1:])
    return x + mixed


class Layer(HybridBlock):
    """h + mixer(RMSNorm(h)); subclasses give `mix`.  Parameters named in
    `_FLOAT32` keep float32 under `cast`, as the published model keeps
    them."""

    _FLOAT32 = ()

    def __init__(self, hidden_size, eps, norm_offset=0.0, **kwargs):
        super().__init__(**kwargs)
        self._hidden, self._eps, self._offset = hidden_size, eps, norm_offset
        with self.name_scope():
            self.norm_weight = self._norm_gain("norm_weight")

    def _norm_gain(self, name):
        """A gain that starts the norm at identity."""
        return self.params.get(name, shape=(self._hidden,),
                               init="zeros" if self._offset else "ones")

    def cast(self, dtype):
        self._clear_cached_op()
        for name, p in self._reg_params.items():
            p.cast(FP32 if name in self._FLOAT32 else dtype)

    def hybrid_forward(self, F, x, norm_weight, **params):
        return norm_residual(F, x, norm_weight, self._eps, self.mix,
                             offset=self._offset, **params)


class Head(HybridBlock):
    """Final RMSNorm and the untied vocabulary projection; with
    `logits_dtype` the product's operands are cast to it first (float32
    logits from bfloat16 weights)."""

    def __init__(self, hidden_size, vocab_size, eps, norm_offset=0.0,
                 logits_dtype=None, **kwargs):
        super().__init__(**kwargs)
        self._eps, self._offset, self._dtype = eps, norm_offset, logits_dtype
        with self.name_scope():
            self.norm_weight = self.params.get(
                "norm_weight", shape=(hidden_size,),
                init="zeros" if norm_offset else "ones")
            self.weight = self.params.get(
                "weight", shape=(vocab_size, hidden_size))

    def hybrid_forward(self, F, x, norm_weight, weight):
        x = F.RMSNorm(x, norm_weight, eps=self._eps, offset=self._offset)
        if self._dtype is not None:
            x, weight = (F.cast(a, dtype=self._dtype) for a in (x, weight))
        return project(F, x, weight)
