"""The step program names its own work (PR 24): block, op, loss and update
scopes in the compiled step's ``op_name`` metadata, handed out through
``parallel.spmd.step_programs()``; ``mx.step.*`` host spans in a
``jax.profiler`` trace; and none of it changes a number or costs a step
anything."""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.block import HybridBlock
from mxnet_tpu.gluon.model_zoo.bert import BERTEncoderCell
from mxnet_tpu.parallel import spmd
from mxnet_tpu.telemetry import tracing

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def conv_net():
    """conv + BatchNorm + dense under SGD; children made inside the
    parent's name scope, so their names repeat its prefix."""
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1, layout="NHWC"),
                nn.BatchNorm(axis=3), nn.Activation("relu"),
                nn.GlobalAvgPool2D(layout="NHWC"), nn.Dense(4))
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    with mx.autograd.pause():
        net(mx.nd.zeros((1, 8, 8, 3), ctx=mx.cpu()))
    trainer = parallel.SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=parallel.make_mesh(dp=1))
    rng = np.random.RandomState(0)
    return trainer, (rng.rand(8, 8, 8, 3).astype("float32"),
                     rng.randint(0, 4, 8).astype(np.int32))


class TwoLayers(HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.layer0 = BERTEncoderCell(16, 32, 2, dropout=0.1,
                                          prefix="layer0_")
            self.layer1 = BERTEncoderCell(16, 32, 2, dropout=0.1,
                                          prefix="layer1_")
        # made outside the name scope: its name does not start with the
        # parent's prefix, so its scope is its whole name
        self.head = nn.Dense(4, prefix="classifier_")

    def hybrid_forward(self, F, x, mask):
        return self.head(self.layer1(self.layer0(x, mask), mask))


def attention_net():
    """Two post-LN encoder layers with attention dropout under Adam."""
    np.random.seed(0)
    mx.random.seed(0)
    net = TwoLayers(prefix="tiny_")
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    with mx.autograd.pause():
        net(mx.nd.zeros((1, 8, 16), ctx=mx.cpu()),
            mx.nd.ones((1, 8), ctx=mx.cpu()))
    trainer = parallel.SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=parallel.make_mesh(dp=1))
    rng = np.random.RandomState(0)
    return trainer, (rng.rand(4, 8, 16).astype("float32"),
                     np.ones((4, 8), "float32"),
                     rng.randint(0, 4, 4).astype(np.int32))


def _table_after_one_step(make):
    trainer, batch = make()
    trainer.step(*batch)
    program = spmd.step_programs()[-1]
    return program, set(program["ops"].values())


def _holds(names, *parts):
    return any(all(p in n for p in parts) for n in names)


def test_conv_step_names_blocks_ops_loss_and_update():
    program, names = _table_after_one_step(conv_net)
    assert program["module"] == "jit_mx_train_step"
    assert program["origin"] == "compiled" and program["scoped"] is True
    # forward under jvp(<outermost scope>), the block path below it with
    # each parent's prefix taken off, the registered op name innermost
    assert _holds(names, "/jvp(net)/conv2d0/Convolution/")
    assert _holds(names, "/jvp(net)/batchnorm0/BatchNorm/")
    assert _holds(names, "/jvp(net)/dense0/FullyConnected/dot_general")
    # backward
    assert _holds(names, "/transpose(jvp(net))/batchnorm0/BatchNorm/")
    assert _holds(names, "/transpose(jvp(net))/dense0/FullyConnected/")
    # the loss (a block itself) and the optimizer, which is outside the
    # gradient and keeps the bare scope
    assert _holds(names, "/jvp(mx.loss)/softmaxcrossentropyloss")
    assert _holds(names, "/transpose(jvp(mx.loss))/")
    assert _holds(names, "jit(mx_train_step)/mx.update/sgd_mom_update/")
    # the one jit in every name is the renamed step
    assert all(n.startswith("jit(mx_train_step)/") for n in names
               if n.startswith("jit("))


def test_attention_step_names_the_attention_core_apart_from_its_matmuls():
    program, names = _table_after_one_step(attention_net)
    assert program["module"] == "jit_mx_train_step" and program["scoped"]
    for way in ("/jvp(tiny)/", "/transpose(jvp(tiny))/"):
        assert _holds(names, way, "layer1/attn/dot_product_attention/")
        assert _holds(names, way, "layer0/attn/query/FullyConnected/")
        assert _holds(names, way, "layer1/ffn/ffn1/FullyConnected/")
    # q/k/v/proj are FullyConnected, never the attention op
    assert not _holds(names, "FullyConnected", "dot_product_attention")
    assert _holds(names, "/jvp(tiny)/layer0/ln1/LayerNorm/")
    # a child named outside its parent's prefix keeps its whole name
    assert _holds(names, "/jvp(tiny)/classifier/FullyConnected/")
    assert _holds(names, "jit(mx_train_step)/mx.update/adam_update/")


class BertShaped(HybridBlock):
    """One encoder layer at a shape the fused training route takes: two
    heads of 64 over 128 positions."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.layer0 = BERTEncoderCell(128, 256, 2, dropout=0.1,
                                          prefix="layer0_")
        self.head = nn.Dense(4, prefix="classifier_")

    def hybrid_forward(self, F, x, mask):
        return self.head(self.layer0(x, mask))


def fused_attention_net():
    np.random.seed(0)
    mx.random.seed(0)
    net = BertShaped(prefix="tiny_")
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    with mx.autograd.pause():
        net(mx.nd.zeros((1, 128, 128), ctx=mx.cpu()),
            mx.nd.ones((1, 128), ctx=mx.cpu()))
    trainer = parallel.SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=parallel.make_mesh(dp=1))
    rng = np.random.RandomState(0)
    return trainer, (rng.rand(2, 128, 128).astype("float32"),
                     np.ones((2, 128), "float32"),
                     rng.randint(0, 4, 2).astype(np.int32))


@pytest.mark.parametrize("interpret", ["0", "1"])
def test_fused_attention_backward_is_booked_to_the_backward_and_the_op(
        monkeypatch, interpret):
    """The fused training route (PR 26) runs its backward from a
    custom_vjp rule.  Its ops (the XLA reference a CPU program lowers to,
    and the kernel's own body under the interpreter) must keep both
    `transpose(` and the op scope, or `attention_device_ms` would fall for
    the wrong reason and `scope_unattributed_pct` would rise."""
    from mxnet_tpu.ops import pallas_attention as pa
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", interpret)
    before = pa.route_counts()
    program, names = _table_after_one_step(fused_attention_net)
    after = pa.route_counts()
    assert after["fused_train"] == before["fused_train"] + 1
    assert after["xla_dropout"] == before["xla_dropout"]
    assert program["scoped"]
    inside = [n for n in names if "attn/dot_product_attention/" in n
              and ("dot_general" in n or "exp" in n)]
    fwd = [n for n in inside if "/jvp(tiny)/" in n]
    bwd = [n for n in inside if "/transpose(jvp(tiny))/" in n]
    # matmuls and the exponential of both passes, nothing outside either
    assert fwd and bwd and len(fwd) + len(bwd) == len(inside)
    assert any("dot_general" in n for n in bwd)
    if interpret == "1":
        assert _holds(names, "/transpose(jvp(tiny))/",
                      "dot_product_attention/", "mx_attention_train_bwd")
        assert _holds(names, "/jvp(tiny)/", "dot_product_attention/",
                      "mx_attention_train_fwd")


_HLO = '''HloModule jit_mx_train_step, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(mx_train_step)/jvp(mx.loss)/reduce_sum"}
}

%fused_computation.1 (p0: f32[8,8], p1: f32[4,8], p2: f32[4,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[4,8]{1,0} parameter(1)
  %p2 = f32[4,8]{1,0} parameter(2)
  %dot.7 = f32[8,8]{1,0} dot(%p1, %p2), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(mx_train_step)/transpose(jvp(net))/dense0/FullyConnected/dot_general"}
  ROOT %sub.3 = f32[8,8]{1,0} subtract(%p0, %dot.7), metadata={op_name="jit(mx_train_step)/mx.update/sgd_update/sub"}
}

%fused_computation.2 (p0.1: f32[4,8]) -> f32[4,8] {
  %p0.1 = f32[4,8]{1,0} parameter(0)
  ROOT %tanh.1 = f32[4,8]{1,0} tanh(%p0.1), metadata={op_name="jit(mx_train_step)/jvp(net)/act0/Activation/tanh"}
}

ENTRY %main.5 (w: f32[8,8], x: f32[4,8]) -> f32[8,8] {
  %w = f32[8,8]{1,0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %x = f32[4,8]{1,0} parameter(1)
  %fusion.2 = f32[4,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(mx_train_step)/jvp(net)/act0/Activation/tanh"}
  %copy.4 = f32[4,8]{1,0} copy(%fusion.2)
  ROOT %fusion.14 = f32[8,8]{1,0} fusion(%w, %x, %copy.4), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(mx_train_step)/mx.update/sgd_update/sub"}
}
'''


def test_a_fusion_holding_a_dot_is_booked_to_the_dot_not_to_its_root():
    table = spmd.program_table(_HLO)
    assert table["module"] == "jit_mx_train_step" and table["scoped"]
    ops = table["ops"]
    # the weight-gradient matmul with the SGD update in its epilogue:
    # XLA's metadata for the fusion is the root's (mx.update)
    assert ops["fusion.14"] == ("jit(mx_train_step)/transpose(jvp(net))/"
                                "dense0/FullyConnected/dot_general")
    # no matmul inside: the fusion's own
    assert ops["fusion.2"] == \
        "jit(mx_train_step)/jvp(net)/act0/Activation/tanh"
    # an instruction with no metadata is in the table, with no scope
    assert ops["copy.4"] == ""
    # what runs inside a fusion is no event of its own in a trace
    assert "dot.7" not in ops and "tanh.1" not in ops
    assert "add.9" in ops       # a reduction's region is no fusion
    stale = spmd.program_table(
        _HLO.replace("mx.update/", "").replace("mx_train_step", "old_step"))
    assert stale["module"] == "jit_old_step" and stale["scoped"] is False


def test_an_instruction_printed_over_several_lines_keeps_its_op_name():
    """XLA prints splash attention's `kernel_metadata` frontend attribute
    with a newline in it: the custom call, and every instruction that
    inherits the attribute, span three lines, the `op_name` on the last."""
    scope = "jit(mx_train_step)/jvp(net)/layer1/sliding_window_attention"
    text = _HLO.replace('  %copy.4 = f32[4,8]{1,0} copy(%fusion.2)\n', (
        '  %splash.1 = f32[4,8]{1,0} custom-call(%fusion.2), '
        'custom_call_target="tpu_custom_call", '
        'frontend_attributes={kernel_metadata={\n'
        '"xprof_metadata":"{\\"block_q\\": 256}"\n'
        '}}, metadata={op_name="' + scope + '/pallas_call"}, '
        'backend_config={"a":{"b":[]}}\n'
        '  %copy.4 = f32[4,8]{1,0} copy(%splash.1), '
        'frontend_attributes={kernel_metadata={\n'
        '"xprof_metadata":"{\\"block_q\\": 256}"\n'
        '}}, metadata={op_name="' + scope + '/copy"}\n'))
    assert text != _HLO
    ops = spmd.program_table(text)["ops"]
    assert ops["splash.1"] == scope + "/pallas_call"
    assert ops["copy.4"] == scope + "/copy"
    # what follows is read as before
    assert ops["fusion.14"] == spmd.program_table(_HLO)["ops"]["fusion.14"]
    assert set(ops) == set(spmd.program_table(_HLO)["ops"]) | {"splash.1"}


# first-step losses of the two seeded nets before any scope existed
# (float32 on this CPU backend): a scope is metadata and changes no
# arithmetic.  The convolution net's is a225b38's; the attention net's is
# PR 26's, whose dropout mask on the attention probabilities comes from
# a hash and no longer from threefry (1.447582721710205 before)
@pytest.mark.parametrize("make, parent_loss", [
    (conv_net, 1.3157033920288086), (attention_net, 1.7128827571868896)])
def test_step_builds_no_table_and_scopes_change_no_arithmetic(
        make, parent_loss):
    before = set(map(id, spmd._STEP_CACHE.data.values()))
    trainer, batch = make()
    assert trainer.step_executable() is None
    loss = float(trainer.step(*batch).asnumpy())
    assert abs(loss - parent_loss) < 1e-5
    trainer.step(*batch).asnumpy()
    mine = [e for e in spmd._STEP_CACHE.data.values()
            if id(e) not in before]
    assert len(mine) == 1
    # stepping rendered no program text: the memo is empty until asked
    assert mine[0].program is None
    assert trainer.step_executable() is mine[0].fn
    assert trainer.step_executable().memory_analysis() is not None
    compiles = spmd.step_compile_stats()
    first = spmd.step_programs()[-1]
    assert mine[0].program is not None
    # memoised per executable, and asking builds or loads nothing
    assert spmd.step_programs()[-1]["ops"] is first["ops"]
    assert spmd.step_compile_stats() == compiles


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                         dict(ev.stats)) for ev in line.events
                        if ev.name.startswith("mx.step")]
    return sorted(out, key=lambda e: (e[0], -e[1]))


def test_profiler_trace_holds_mx_step_with_its_five_children(tmp_path):
    import jax

    assert isinstance(tracing.annotation("x", step=1),
                      jax.profiler.TraceAnnotation)
    trainer, batch = conv_net()
    trainer.step(*batch).asnumpy()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            loss = trainer.step(*batch)
        loss.asnumpy()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    children = ["mx.step.place", "mx.step.scalars", "mx.step.get_step",
                "mx.step.dispatch", "mx.step.rebind"]
    assert [e[2] for e in events] == (["mx.step"] + children) * 3
    for k in range(3):
        (start, end, _name, stats), *kids = events[6 * k:6 * k + 6]
        # the warm-up step was number 1
        assert stats["step"] == k + 2
        assert all(s["step"] == k + 2 for *_x, s in kids)
        # nested in the parent, one after the other
        edges = [start] + [t for s, e, *_x in kids for t in (s, e)] + [end]
        assert edges == sorted(edges)


_CHILD = r"""
import json, os, sys
sys.path.insert(0, os.path.join(os.getcwd(), "tests"))
import jax
from mxnet_tpu.compile_cache import jax_cache
jax_cache.configure()
from test_step_scopes import conv_net
from mxnet_tpu.parallel import spmd
trainer, batch = conv_net()
loss = float(trainer.step(*batch).asnumpy())
p = spmd.step_programs()[-1]
print(json.dumps({"origin": p["origin"], "scoped": p["scoped"],
                  "module": p["module"], "loss": loss,
                  "stats": spmd.step_compile_stats()}))
"""


def test_a_step_loaded_from_the_jax_cache_says_so_and_keeps_its_scopes(
        tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               # JAX's thresholds would keep a toy program out of the cache
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    runs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                           cwd=_REPO, capture_output=True, text=True,
                           timeout=240)
        assert p.returncode == 0, p.stderr[-2000:]
        runs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    first, second = runs
    assert first["origin"] == "compiled" and second["origin"] == "cache"
    # the loaded executable still prints its op_name metadata
    assert first["scoped"] and second["scoped"]
    assert second["module"] == "jit_mx_train_step"
    assert first["loss"] == second["loss"]
    # the counters the benchmark's `correct` reads keep their meaning
    assert first["stats"]["count"] == second["stats"]["count"] == 1
