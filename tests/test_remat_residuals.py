"""A recomputed segment keeps what its attention kernels wrote
(`ops/residuals.py`): the output and the logsumexp are named in
the forward rule of each kernel route's custom VJP, every `jax.checkpoint`
segment of `gluon/block.py` keeps the named values, and the forward kernel
runs once a step where it ran twice.

All on the CPU: the TPU branch of `lax.platform_dependent` is in the
jaxpr whatever the program is lowered for, so the kernels are counted
there; what they compute is held under the interpreter.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.block import CachedOp, HybridBlock
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import residuals
from mxnet_tpu.telemetry import instruments

B, S, H, H_KV, D = 1, 256, 2, 1, 128
WINDOW, CHUNK = 128, 2

# route -> (forward kernel, backward kernels); each keeps o and one
# logsumexp.  The causal and the window cores run the same forward kernel;
# the whole triangle's backward is one kernel of the repo's own where the
# block of 1,024 rows divides S (PR 48), a window's another (PR 50), EVA's
# upstream's two
ROUTES = {
    "flash_causal": ("splash_mqa_fwd_residuals",
                     ("mx_causal_attention_bwd",)),
    "splash_window": ("splash_mqa_fwd_residuals",
                      ("mx_window_attention_bwd",)),
    "eva_splash": ("splash_mha_fwd_residuals",
                   ("splash_mha_dkv_no_residuals",
                    "splash_mha_dq_no_residuals")),
    # latent attention: `flash_causal`'s kernels, one query head a key
    "latent_splash": ("splash_mqa_fwd_residuals",
                      ("mx_causal_attention_bwd",)),
}
# the rows a route's layer is run at: one block of the triangle's kernels
ROWS = {"flash_causal": 1024, "latent_splash": 1024, "splash_window": S,
        "eva_splash": S}
ROPE = 64      # the latent queries' and keys' rotary part, beside D


class Layer(HybridBlock):
    """A layer that owns its parameters, so one recomputed segment:
    projections, the attention core of `route`, a projection."""

    def __init__(self, route, **kwargs):
        super().__init__(**kwargs)
        self._route = route
        # eva_attention and latent_attention have no grouped-query form
        self._kv = H_KV if route in ("flash_causal", "splash_window") else H
        q_size = D + ROPE if route == "latent_splash" else D
        with self.name_scope():
            for name, rows in (("q", H * q_size), ("k", self._kv * D),
                               ("v", self._kv * D), ("o", H * D),
                               ("k_rope", ROPE)):
                setattr(self, name, self.params.get(
                    name, shape=(rows, H * D),
                    init=mx.initializer.Normal(0.05)))
            for name in ("phi", "mu"):
                setattr(self, name, self.params.get(
                    name, shape=(H, D), init=mx.initializer.Normal(0.05)))

    def hybrid_forward(self, F, x, q, k, v, o, k_rope, phi, mu):
        q, k, v, k_rope = (F.FullyConnected(x, w, no_bias=True,
                                            flatten=False,
                                            num_hidden=w.shape[0])
                           for w in (q, k, v, k_rope))
        if self._route == "latent_splash":
            out = F.latent_attention(q, k, k_rope, v, num_heads=H)
        elif self._route == "flash_causal":
            out = F.dot_product_attention(q, k, v, None, causal=True,
                                          num_heads=H, num_kv_heads=self._kv)
        elif self._route == "splash_window":
            out = F.sliding_window_attention(q, k, v, window=WINDOW,
                                             num_heads=H,
                                             num_kv_heads=self._kv)
        else:
            ks, vs = F.eva_chunk_summary(k, v, phi, mu, num_heads=H,
                                         chunk=CHUNK)
            out = F.eva_attention(q, k, v, ks, vs, num_heads=H,
                                  window=WINDOW, chunk=CHUNK)
        return F.FullyConnected(out, o, no_bias=True, flatten=False,
                                num_hidden=H * D)


def _layer(route):
    layer = Layer(route)
    np.random.seed(0)
    layer.initialize()
    return layer


def _gradient(block, dtype=jnp.float32, rows=S):
    """(value-and-gradient of a loss through the initialised `block`
    under gradient mirroring, its parameters, an input of `rows`
    positions)."""
    op = CachedOp(block, mirror=True)
    pure = op._make_pure(True)
    params = tuple(p.data().data.astype(dtype) for _, p in op._param_list())
    x = jnp.asarray(np.random.RandomState(0).randn(B, rows, H * D), dtype)
    weight = jnp.cos(jnp.arange(H * D, dtype=jnp.float32))

    def loss(params, x):
        (out,), _ = pure(params, (x,), jnp.zeros((2,), jnp.uint32))
        return (out.astype(jnp.float32) * weight).sum()

    return jax.value_and_grad(loss, argnums=(0, 1)), params, x


def _count(jaxpr, into=None):
    """Counter of what a jaxpr runs, sub-jaxprs included: Pallas kernels
    by name, every other primitive as `xla:<name>` (a kernel's body is
    not entered)."""
    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            into[eqn.params["name"]
                 or eqn.params["jaxpr"].debug_info.func_name] += 1
            continue
        into[f"xla:{eqn.primitive.name}"] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count(sub, into)
    return into


def _drop_policy(monkeypatch):
    """The segment as it was before the policy: everything recomputed."""
    monkeypatch.setattr(residuals, "KEEP_NAMED", None)


@pytest.mark.parametrize("route", list(ROUTES))
def test_the_forward_kernel_appears_once_where_it_appeared_twice(
        route, monkeypatch):
    forward, backward = ROUTES[route]
    before = residuals.kept_residuals()[route]
    layer = _layer(route)
    fn, params, x = _gradient(layer, rows=ROWS[route])
    kept = _count(jax.make_jaxpr(fn)(params, x).jaxpr)
    after = residuals.kept_residuals()[route]
    _drop_policy(monkeypatch)
    fn, params, x = _gradient(layer, rows=ROWS[route])
    recomputed = _count(jax.make_jaxpr(fn)(params, x).jaxpr)

    assert recomputed[forward] == 2
    assert kept[forward] == 1
    for name in backward:
        assert kept[name] == recomputed[name] == 1
    # o and the logsumexp, named in the forward rule (recomputed, the
    # forward pass names o and the backward pass both again) ...
    assert kept["xla:name"] == 2
    assert recomputed["xla:name"] == 3
    # ... and counted as kept, with their bytes: o in the layer's dtype,
    # float32 rows of logsumexp
    assert after["values"] - before["values"] == 2
    assert after["bytes"] - before["bytes"] == B * H * ROWS[route] * (
        D * 4 + 4)
    # everything else in the segment is still computed again: the
    # projections, the head split, the XLA twin in the other branch
    for name in ("xla:dot_general", "xla:transpose", "xla:exp"):
        assert kept[name] == recomputed[name] > 0, name


@pytest.mark.parametrize("route", list(ROUTES))
def test_the_xla_twins_name_nothing_and_are_recomputed_whole(
        route, monkeypatch):
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    before = residuals.kept_residuals()
    layer = _layer(route)
    fn, params, x = _gradient(layer, rows=ROWS[route])
    kept = _count(jax.make_jaxpr(fn)(params, x).jaxpr)
    assert residuals.kept_residuals() == before
    _drop_policy(monkeypatch)
    fn, params, x = _gradient(layer, rows=ROWS[route])
    assert kept == _count(jax.make_jaxpr(fn)(params, x).jaxpr)
    assert kept["xla:name"] == 0 and kept["xla:dot_general"] > 0
    assert not any(not k.startswith("xla:") for k in kept)


@pytest.mark.parametrize("route", list(ROUTES))
def test_interpreted_gradients_are_those_of_the_recomputed_kernel(
        route, monkeypatch):
    """The kept o and logsumexp are the values the second call of the
    kernel would have produced: loss and every gradient exactly equal."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    layer = _layer(route)
    fn, params, x = _gradient(layer, rows=ROWS[route])
    kept = jax.jit(fn)(params, x)
    _drop_policy(monkeypatch)
    fn, params, x = _gradient(layer, rows=ROWS[route])
    recomputed = jax.jit(fn)(params, x)
    assert np.isfinite(kept[0]) and np.abs(kept[1][1]).max() > 0
    for a, b in zip(jax.tree_util.tree_leaves(kept),
                    jax.tree_util.tree_leaves(recomputed)):
        np.testing.assert_array_equal(a, b)


def test_the_causal_route_runs_upstreams_kernels_at_the_routes_blocks():
    """`_causal_splash` is upstream's multi-query kernel over a
    `CausalMask` at the blocks `_splash_blocks` gives, on q scaled
    beforehand: value and gradients bit for bit those of upstream's
    kernel called directly, and `_causal_xla`'s to rounding."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    k, v = (jnp.asarray(rng.randn(B, H_KV, S, D), jnp.float32)
            for _ in range(2))
    rows, compute = pa._splash_blocks(S, None)
    kernel = sa.make_splash_mqa_single_device(
        sa.MultiHeadMask([sa.CausalMask((S, S))] * (H // H_KV)),
        block_sizes=sa.BlockSizes(
            block_q=rows, block_kv=rows, block_kv_compute=compute,
            block_q_dkv=rows, block_kv_dkv=rows,
            block_kv_dkv_compute=compute, block_q_dq=rows, block_kv_dq=rows),
        interpret=True)
    weight = jnp.cos(jnp.arange(D, dtype=jnp.float32))
    cores = (lambda q, k, v: kernel(q[0] * jnp.float32(0.1), k[0, 0],
                                    v[0, 0])[None],
             lambda q, k, v: pa._causal_splash(q, k, v, 0.1, interpret=True))
    upstream, ours = (jax.value_and_grad(
        lambda q, k, v: (core(q, k, v) * weight).sum(),
        argnums=(0, 1, 2))(q, k, v) for core in cores)
    np.testing.assert_array_equal(cores[0](q, k, v), cores[1](q, k, v))
    for a, b in zip(jax.tree_util.tree_leaves(upstream),
                    jax.tree_util.tree_leaves(ours)):
        np.testing.assert_array_equal(a, b)
    want = jax.grad(lambda q, k, v: (pa._causal_xla(q, k, v, 0.1)
                                     * weight).sum(), argnums=(0, 1, 2))
    for a, b in zip(ours[1], want(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class _Lfm2Segment(HybridBlock):
    """One `lfm2.Lfm2Layer` (it owns its parameters: one segment) under a
    container without any, which hands it the rotary tables."""

    def __init__(self, kind, **kwargs):
        super().__init__(**kwargs)
        from mxnet_tpu.gluon.model_zoo.lfm2 import Lfm2Layer

        with self.name_scope():
            self.layer = Lfm2Layer(H * D, 1e-5, kind, num_heads=H * D // 64,
                                   num_kv_heads=H_KV, mlp_size=64)

    def hybrid_forward(self, F, x):
        from mxnet_tpu.ops import rotary

        return self.layer(x, *rotary.rotary_tables(
            rotary.default_inv_freq(1e6, 64), x.shape[1]))


def _lfm2_segment(kind):
    block = _Lfm2Segment(kind)
    np.random.seed(0)
    block.initialize(mx.initializer.Normal(0.05))
    return block


def test_a_conv_layers_segment_keeps_nothing_and_is_recomputed_whole(
        monkeypatch):
    """`short_conv` is plain XLA with a backward rule of its own: it
    names nothing, so the layer's segment keeps its input alone and the
    policy changes nothing of what it runs."""
    before = residuals.kept_residuals()
    block = _lfm2_segment("conv")
    fn, params, x = _gradient(block)
    kept = _count(jax.make_jaxpr(fn)(params, x).jaxpr)
    assert residuals.kept_residuals() == before
    _drop_policy(monkeypatch)
    fn, params, x = _gradient(block)
    assert kept == _count(jax.make_jaxpr(fn)(params, x).jaxpr)
    assert kept["xla:name"] == 0 and kept["xla:remat2"] == 1
    assert kept["xla:custom_vjp_call"] + kept["xla:custom_vjp_call_jaxpr"] \
        >= 1                                    # short_conv's own rule
    assert not any(not k.startswith("xla:") for k in kept)


def test_an_attention_layers_segment_at_head_size_64_keeps_o_and_logsumexp(
        monkeypatch):
    """The 64-wide grouped causal core takes `flash_causal`'s kernels:
    the layer's segment (q/k norms, rotation, core, projections, MLP)
    keeps o and the logsumexp and runs the forward kernel once."""
    forward, backward = ROUTES["flash_causal"]
    heads = H * D // 64
    before = residuals.kept_residuals()["flash_causal"]
    block = _lfm2_segment("full_attention")
    fn, params, x = _gradient(block, rows=1024)
    kept = _count(jax.make_jaxpr(fn)(params, x).jaxpr)
    after = residuals.kept_residuals()["flash_causal"]
    _drop_policy(monkeypatch)
    fn, params, x = _gradient(block, rows=1024)
    recomputed = _count(jax.make_jaxpr(fn)(params, x).jaxpr)
    assert (kept[forward], recomputed[forward]) == (1, 2)
    for name in backward:
        assert kept[name] == recomputed[name] == 1
    assert after["values"] - before["values"] == 2
    assert after["bytes"] - before["bytes"] == B * heads * 1024 * (64 * 4 + 4)
    # the norms a head and the rotation are computed again
    for name in ("xla:rsqrt", "xla:dot_general"):
        assert kept[name] == recomputed[name] > 0, name


def test_a_segment_that_names_nothing_lowers_to_the_program_it_did(
        monkeypatch):
    def program():
        # named, not numbered: the parameters go in by sorted name, and
        # Gluon's counter would put `dense10_` before `dense9_` where an
        # earlier test of the same process left it at 9 (PR 40's run)
        net = nn.HybridSequential(prefix="net_")
        net.add(nn.Dense(16, activation="relu", in_units=8,
                         prefix="net_first_"),
                nn.Dense(4, in_units=16, prefix="net_second_"))
        np.random.seed(0)
        net.initialize(mx.initializer.Xavier())
        op = CachedOp(net, mirror=True)
        pure = op._make_pure(True)
        params = tuple(p.data().data for _, p in op._param_list())
        loss = lambda params, x: (pure(params, (x,), jnp.zeros(
            (2,), jnp.uint32))[0][0] ** 2).sum()
        lowered = jax.jit(jax.value_and_grad(loss)).lower(
            params, jnp.ones((4, 8), jnp.float32))
        return lowered.as_text(), str(jax.make_jaxpr(jax.grad(loss))(
            params, jnp.ones((4, 8), jnp.float32)))

    before = residuals.kept_residuals()
    text, jaxpr = program()
    assert "save_only_these_names" in jaxpr     # a segment, with the policy
    assert residuals.kept_residuals() == before
    _drop_policy(monkeypatch)
    assert program()[0] == text


def test_outside_a_segment_a_name_keeps_nothing_and_counts_nothing():
    """Inference, and training without gradient mirroring: the forward
    rule's names are identities and the counter stays where it was."""
    before = residuals.kept_residuals()
    op = CachedOp(_layer("splash_window"), mirror=False)
    pure = op._make_pure(True)
    params = tuple(p.data().data for _, p in op._param_list())
    loss = lambda params, x: pure(params, (x,), jnp.zeros(
        (2,), jnp.uint32))[0][0].sum()
    counts = _count(jax.make_jaxpr(jax.grad(loss))(
        params, jnp.ones((B, S, H * D), jnp.float32)).jaxpr)
    assert counts["splash_mqa_fwd_residuals"] == 1
    assert counts["xla:remat2"] == 0
    assert residuals.kept_residuals() == before


def test_kept_bytes_are_exported_and_unknown_names_refused():
    telemetry.enable()
    try:
        child = instruments.remat_kept_bytes_total("flash_causal")
        before = child.value
        residuals.note("flash_causal", 1, 1536)         # no segment: nothing
        assert child.value == before
        with residuals.segment():
            residuals.note("flash_causal", 2, 3072)
        assert child.value == before + 3072
    finally:
        telemetry.disable()
    with pytest.raises(ValueError, match="not a residual name"):
        residuals.note("projection", 1, 1536)
    assert set(residuals.NAMES) <= set(pa.ROUTES)
