"""Layer: kernels.  Device time per step on chip 0, forward and backward
(with the forward done again under remat), of the ops whose op scope is
`latent_projection`: the low-rank chains x -> q, k_nope, k_r, v with
their two inner RMSNorms, an op of its own so that they read apart from
the `FullyConnected` of the output projection, the dense MLP, the shared
expert, the module's join and the head."""
from harness import scope_time


def read(run):
    return scope_time.op_ms(run, "latent_projection")
