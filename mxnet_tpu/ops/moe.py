"""The expert layer's two stages as registered ops, so that a traced
program books their work under `moe_route` and `moe_experts`
(`ops.registry.apply_pure`).  The arithmetic is `parallel/moe.py`'s.
"""
from __future__ import annotations

from .registry import register_op


@register_op("moe_route", num_outputs=4)
def _moe_route(data, router_weight, router_bias, top_k=1, scale=1.0,
               first_expert=0, num_local=0):
    """Route tokens `data` (T, D) over all rows of `router_weight` (E, D)
    and lay out the assignments onto the `num_local` experts held from
    `first_expert` on (all E where 0).  Returns (token of each row, its
    weight, rows per held expert, dropped assignments = 0), the rows
    expert by expert and token-ascending inside an expert: see
    `parallel.moe.route`."""
    from ..parallel import moe

    return tuple(moe.route(data, router_weight, router_bias, top_k=top_k,
                           scale=scale, first_expert=first_expert,
                           n_local=num_local or None))


@register_op("moe_experts")
def _moe_experts(data, token, weight, group_sizes, w1, w2, form="relu2",
                 expected_rows=0):
    """The held experts' part for tokens `data` (T, K) under a plan from
    `moe_route`, over a token's held experts; w2 (n, N, K).  `form`
    "relu2": sum of weight * relu(data W1_e)^2 W2_e, w1 (n, K, N);
    "silu_gated": sum of weight * (silu(data G_e) * (data U_e)) W2_e, w1
    (n, K, 2N) = [G | U].  `expected_rows`: the assignments the model
    expects on the held experts (`parallel.moe.row_chunk`)."""
    import jax.numpy as jnp

    from ..parallel import moe

    plan = moe.RoutePlan(token, weight, group_sizes, jnp.zeros((), jnp.int32))
    return moe.experts(data, plan, w1, w2, form, expected_rows)
