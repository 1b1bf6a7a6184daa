"""ops/kernel_route.py: the one rule that admits a kernel, the one
`platform_dependent`, the one store of routes chosen and the one
`shard_map` wrap; and that no other module of `ops/` or `parallel/`
decides any of it again.  What each ROUTE does under the rule stays in
the route's own tests (test_attention_train.py, test_rotary_kernel.py,
test_ssd_kernel.py, test_moe_blocks.py, ...), and that every route
compiles its kernel for a described v5e through `dispatch` in
test_chip_smoke.py's ahead-of-time cases."""
import ast
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import parallel, telemetry
from mxnet_tpu.ops import kernel_route as kr
from mxnet_tpu.ops import pallas_attention, residuals, rotary, ssm
from mxnet_tpu.parallel import moe

BARE = kr.Kernel("test_family", "kernel", "twin")
SHARDED = kr.Kernel("test_family", "kernel", "twin", kr.BATCH_SHARDS)
UNASKED = kr.Kernel("test_family", "kernel", "twin", kr.ANY_MESH)
kr.declare("test_family", ("kernel", "twin", "other"))

NONE, SHARD = "one bare call", "one call a batch shard"


@pytest.mark.parametrize("knob,mesh,batch,supports,kernel,want", [
    # MXNET_USE_PALLAS=0 selects the twin whatever else holds
    ("0", None, 8, True, BARE, False),
    ("0", dict(dp=4), 8, True, SHARDED, False),
    ("0", None, 8, True, UNASKED, False),
    # no mesh, or a mesh of one device: every kernel, one bare call
    ("1", None, 8, True, BARE, NONE),
    ("1", dict(dp=1), 8, True, BARE, NONE),
    ("1", None, 8, True, SHARDED, NONE),
    # dp / fsdp alone split the batch evenly: the kernel that takes a
    # shard runs one call a shard, the one that does not gives way
    ("1", dict(dp=4), 8, True, BARE, False),
    ("1", dict(dp=4), 8, True, SHARDED, SHARD),
    ("1", dict(dp=2, fsdp=4), 16, True, SHARDED, SHARD),
    # anything else (another axis, a batch that does not split): the twin
    ("1", dict(dp=4, tp=2), 8, True, SHARDED, False),
    ("1", dict(dp=2, sp=4), 8, True, BARE, False),
    ("1", dict(dp=8), 12, True, SHARDED, False),
    # a kernel that does not ask the mesh is a bare call under any
    ("1", dict(dp=4, tp=2), 8, True, UNASKED, NONE),
    # a shape the route does not support: the twin, whatever the mesh
    ("1", None, 8, False, BARE, False),
    ("1", dict(dp=4), 8, None, SHARDED, False),
    ("1", None, 8, 0, UNASKED, False),
])
def test_the_admission_rule(monkeypatch, knob, mesh, batch, supports, kernel,
                            want):
    monkeypatch.setenv("MXNET_USE_PALLAS", knob)
    before = kr.counts("test_family")

    def ask():
        return kr.admit(kernel, supports, batch), kr.choose(
            kernel, supports, batch)

    if mesh is None:
        admitted, chosen = ask()
    else:
        with parallel.make_mesh(mesh) as m:
            admitted, chosen = ask()
    assert admitted == chosen
    if want is SHARD:
        assert admitted == (m.mesh, tuple(a for a in ("dp", "fsdp")
                                          if mesh.get(a, 1) > 1))
    else:
        assert admitted is {NONE: True, False: False}[want]
    # `admit` counts nothing; `choose` counts the name of what runs
    after = kr.counts("test_family")
    counted = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    assert counted == {"twin" if want is False else "kernel": 1}


def test_the_interpreter_switch_is_read_at_the_call(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    assert kr.interpret() is True
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "0")
    assert kr.interpret() is False


def _lowered(fn, platform, *args):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=(platform,)).as_text()


def test_dispatch_runs_the_kernel_under_the_interpreter_and_by_platform():
    """Lowered for the CPU the twin, for the TPU the kernel, under the
    interpreter the kernel anywhere; autodiff goes through the branch
    taken."""
    kernel, twin = (lambda x: x * 3.0), (lambda x: x + 1.0)
    x = jnp.arange(4.0)

    def routed(interpret):
        return lambda x: kr.dispatch(kernel, twin, x,
                                     interpret=interpret).sum()

    np.testing.assert_array_equal(
        kr.dispatch(kernel, twin, x, interpret=True), x * 3.0)
    np.testing.assert_array_equal(
        kr.dispatch(kernel, twin, x, interpret=False), x + 1.0)     # the CPU
    np.testing.assert_array_equal(jax.grad(routed(True))(x), np.full(4, 3.0))
    np.testing.assert_array_equal(jax.grad(routed(False))(x), np.ones(4))
    # the program itself: which branch a platform's lowering selects
    index = lambda text: re.search(
        r"stablehlo\.constant dense<(\d)> : tensor<i32>", text).group(1)
    assert index(_lowered(routed(False), "cpu", x)) != index(
        _lowered(routed(False), "tpu", x))
    assert "stablehlo.case" not in _lowered(routed(True), "tpu", x)


@pytest.mark.parametrize("platform,mosaic", [("cpu", False), ("tpu", True)])
def test_a_route_lowers_its_kernel_for_the_tpu_and_its_twin_for_the_cpu(
        platform, mosaic):
    """A real route through `dispatch`, lowered ahead of time for either
    platform with no chip and no TPU compiler: the rotation's Mosaic call
    is in the TPU's program and not in the CPU's, value and gradient."""
    x = jax.ShapeDtypeStruct((2, 256, 4 * 64), jnp.bfloat16)
    table = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    before = rotary.route_counts()

    def loss(x, cos, sin):
        q, k = rotary._rotary_embedding(x, x, cos, sin, num_heads=4)
        return (q.astype(jnp.float32) * k.astype(jnp.float32)).sum()

    text = _lowered(jax.grad(loss), platform, x, table, table)
    assert ("tpu_custom_call" in text) is mosaic
    assert ("mx_rotary_turn" in text) is mosaic
    # the route is chosen at trace time, whatever the platform
    assert rotary.route_counts()["kernel"] == before["kernel"] + 2


def test_one_store_behind_the_four_public_views():
    """The views keep exactly the keys they had, in their order, and are
    copies; a count shows in its family's view alone."""
    assert tuple(pallas_attention.route_counts()) == pallas_attention.ROUTES
    assert tuple(ssm.route_counts()) == ssm.ROUTES == (
        "chunked_xla", "fused_kernel")
    assert tuple(rotary.route_counts()) == rotary.ROUTES == ("kernel", "xla")
    assert tuple(moe.route_counts()) == moe.ROUTES + (
        "sorted_layout", "expert_stage_traces", "token_sums", "exact_tiles",
        "padded_tiles")
    assert set(residuals.NAMES) <= set(pallas_attention.ROUTES)
    views = (pallas_attention, ssm, rotary, moe)
    before = [v.route_counts() for v in views]
    before[0]["reference"] += 100           # a copy: the store is not moved
    kr.count("ssd_scan", "chunked_xla", 2)
    after = [v.route_counts() for v in views]
    before[0]["reference"] -= 100
    before[1]["chunked_xla"] += 2
    assert after == before
    with pytest.raises(KeyError):
        kr.count("ssd_scan", "no_such_route")


@pytest.mark.parametrize("family,metric,route", [
    ("attention", "mx_attention_route_total", "latent_xla"),
    ("rotary", "mx_rotary_route_total", "xla"),
    ("dropout", "mx_dropout_sites_total", "hash"),
])
def test_the_exported_families_reach_telemetry(family, metric, route):
    telemetry.enable()
    try:
        fam = lambda: telemetry.get_registry().get(metric)
        exported = fam().labels(route).value if fam() else 0
        before = kr.counts(family)[route]
        kr.count(family, route, 3)
        assert kr.counts(family)[route] == before + 3
        assert fam().labels(route).value == exported + 3
    finally:
        telemetry.disable()


def test_a_route_that_names_its_residuals_notes_them_inside_a_segment():
    kernel = kr.Kernel("attention", "eva_splash", "eva_xla")
    before = residuals.kept_residuals()["eva_splash"]
    kr.choose(kernel, True, 2, kept=(2, 640))       # outside a segment
    assert residuals.kept_residuals()["eva_splash"] == before
    with residuals.segment():
        kr.choose(kernel, True, 2, kept=(2, 640))
        kr.choose(kernel, False, 2, kept=(2, 640))  # the twin names nothing
    assert residuals.kept_residuals()["eva_splash"] == {
        "values": before["values"] + 2, "bytes": before["bytes"] + 640}


@pytest.mark.parametrize("axes", [dict(dp=8), dict(dp=2, fsdp=4)])
def test_per_batch_shard_is_one_call_a_shard_with_the_callers_specs(axes):
    """Operands and result split over the batch axes and nothing else,
    the operands named `replicated` whole on every device, `first_row`
    the shard's first GLOBAL row; a bare call is one call from row 0."""
    from jax.sharding import PartitionSpec as P

    rows = jnp.arange(16.0)[:, None] * jnp.ones((1, 3))
    key = jnp.asarray([5.0, 7.0])
    seen = []

    def fn(first_row, rows, key, more):
        seen.append((rows.shape, key.shape, more.shape))
        return rows + first_row + key.sum() + more

    want = rows + 12.0 + rows
    np.testing.assert_array_equal(
        kr.per_batch_shard(fn, True, rows, key, rows, replicated=(1,)),
        want)                                       # first_row 0
    assert seen == [((16, 3), (2,), (16, 3))]
    with parallel.make_mesh(axes):
        shard = kr.admit(SHARDED, True, 16)
        assert shard == kr.mesh_batch_axes(16)
    sharded = lambda *a: kr.per_batch_shard(fn, shard, *a, replicated=(1,))
    first = jnp.repeat(jnp.arange(0.0, 16.0, 2.0), 2)[:, None]
    np.testing.assert_array_equal(sharded(rows, key, rows), want + first)
    assert seen[-1] == ((2, 3), (2,), (2, 3))
    eqn, = (e for e in jax.make_jaxpr(sharded)(rows, key, rows).eqns
            if e.primitive.name == "shard_map")
    batch = P(shard[1])
    assert tuple(eqn.params["in_specs"]) == (batch, P(), batch)
    assert tuple(eqn.params["out_specs"]) == (batch,)
    assert eqn.params["mesh"] is shard[0] or eqn.params["mesh"] == shard[0]


# `pallas_convbn.py` keeps its own read of MXNET_PALLAS_INTERPRET and its
# probe-and-latch on purpose: a kernel behind a user-set knob
# (MXNET_FUSED_CONVBN) that the chip refused at the benchmark's batch,
# waiting for its verdict (ROADMAP.md Queue 3 item 2); deleting it must
# touch nothing that lives.
_OUTSIDE = {"kernel_route.py", "pallas_convbn.py"}
_KNOBS = {"MXNET_USE_PALLAS", "MXNET_PALLAS_INTERPRET"}
_MOVED = {"_mesh_batch_axes", "_count_route", "_count_kernel_route"}


def _decisions(tree):
    """What a module's CODE holds of the decision: calls of
    `platform_dependent`, the knobs' names as strings of their own (a
    docstring that mentions one is not a read), and the private names
    that lived in pallas_attention."""
    for node in ast.walk(tree):
        name = getattr(node, "attr", None) or getattr(node, "id", None) \
            or getattr(node, "name", None)
        if isinstance(node, ast.Call) and getattr(
                node.func, "attr", getattr(node.func, "id", "")) \
                == "platform_dependent":
            yield "platform_dependent()"
        elif isinstance(node, ast.Constant) and node.value in _KNOBS:
            yield node.value
        elif name in _MOVED:
            yield name


def test_no_other_module_of_ops_or_parallel_decides_a_route():
    """`lax.platform_dependent` is called, and the two knobs are read, in
    kernel_route.py alone; nobody reaches into pallas_attention for the
    mesh question or the count."""
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "mxnet_tpu")
    found, seen = [], 0
    for package in ("ops", "parallel"):
        for name in sorted(os.listdir(os.path.join(root, package))):
            if name.endswith(".py") and name not in _OUTSIDE:
                with open(os.path.join(root, package, name)) as f:
                    tree = ast.parse(f.read())
                found += [f"{package}/{name}: {what}"
                          for what in _decisions(tree)]
                seen += 1
    assert seen > 25 and not found, found
    with open(os.path.join(root, "ops", "kernel_route.py")) as f:
        own = list(_decisions(ast.parse(f.read())))
    assert sorted(own) == ["MXNET_PALLAS_INTERPRET", "MXNET_USE_PALLAS",
                           "platform_dependent()"]
