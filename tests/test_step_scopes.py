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


def conv_net(remat=False):
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
        mesh=parallel.make_mesh(dp=1), remat=remat)
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


def attention_net(remat=False):
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
        {"learning_rate": 1e-3}, mesh=parallel.make_mesh(dp=1),
        remat=remat)
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


_SGD = "jit(mx_train_step)/mx.update/sgd_update"
_WGRAD = "jit(mx_train_step)/transpose(jvp(net))/dense0/FullyConnected"


def test_an_instruction_says_what_it_is_and_ops_keeps_its_values():
    table = spmd.program_table(_HLO)
    assert table["ops"] == {
        "a": "", "b": "", "add.9": "jit(mx_train_step)/jvp(mx.loss)/reduce_sum",
        "w": "params[\\'w\\']", "x": "",
        "fusion.2": "jit(mx_train_step)/jvp(net)/act0/Activation/tanh",
        "copy.4": "", "fusion.14": _WGRAD + "/dot_general"}
    records = table["instructions"]
    assert list(records) == list(table["ops"])
    # rule B's missing half: the weight gradient's fusion holds the
    # update too, its own (the root's) name first, the primitive dropped
    assert records["fusion.14"] == {
        "opcode": "fusion", "scopes": [_SGD, _WGRAD], "pass": "backward",
        "passes": ["backward", "update"], "flops": 2 * 8 * 8 * 4,
        "kernel": None}
    assert records["fusion.2"] == {
        "opcode": "fusion",
        "scopes": ["jit(mx_train_step)/jvp(net)/act0/Activation"],
        "pass": "forward", "passes": ["forward"], "flops": 0,
        "kernel": None}
    assert records["copy.4"] == {
        "opcode": "copy", "scopes": [], "pass": "other",
        "passes": ["other"], "flops": 0, "kernel": None}
    assert records["w"]["opcode"] == "parameter"
    assert records["w"]["scopes"] == ["params[\\'w\\']"]
    assert records["add.9"]["opcode"] == "add"


def _with(line, text=_HLO):
    """`text` with one more instruction before the entry's copy."""
    copy = "  %copy.4 = f32[4,8]{1,0} copy(%fusion.2)\n"
    assert copy in text
    return text.replace(copy, line + "\n" + copy)


@pytest.mark.parametrize("name, kernel", [
    ("splash_mqa_fwd_residuals.3", "splash_mqa_fwd_residuals"),
    ("mx_causal_attention_bwd", "mx_causal_attention_bwd"),
    ("gmm.12.clone.1", "gmm.12.clone"), ("gmm.7", "gmm")])
def test_a_mosaic_call_is_a_kernel_by_its_name_less_the_numbering(
        name, kernel):
    scope = "jit(mx_train_step)/jvp(net)/layer1/dot_product_attention"
    # printed over three lines, as splash's `kernel_metadata` is; XLA's
    # own rewrite of `ragged_dot` has no metadata at all
    text = _with(
        f"  %{name} = f32[4,8]{{1,0}} custom-call(%fusion.2), "
        'custom_call_target="tpu_custom_call", '
        "frontend_attributes={kernel_metadata={\n"
        '"xprof_metadata":"{\\"block_q\\": 256}"\n'
        '}}, metadata={op_name="' + scope + '/pallas_call"}')
    text = _with('  %ragged.5 = f32[4,8]{1,0} custom-call(%fusion.2), '
                 'custom_call_target="tpu_custom_call"', text)
    text = _with('  %concat.1 = f32[4,8]{1,0} custom-call(%fusion.2), '
                 'custom_call_target="ConcatBitcast"', text)
    table = spmd.program_table(text)
    record = table["instructions"][name]
    assert record == {"opcode": "custom-call", "scopes": [scope],
                      "pass": "forward", "passes": ["forward"], "flops": 0,
                      "kernel": kernel}
    assert table["instructions"]["ragged.5"]["kernel"] == "ragged"
    assert table["instructions"]["ragged.5"]["scopes"] == []
    assert table["instructions"]["concat.1"]["kernel"] is None
    assert table["ops"]["fusion.14"] == _WGRAD + "/dot_general"


_CONVOLUTIONS = {
    # a forward 3x3, stride 2, padded: outputs x taps x input features
    "fwd": ("bf16[2,4,4,16]", "bf16[2,8,8,8]", "bf16[3,3,8,16]",
            "window={size=3x3 stride=2x2 pad=1_1x1_1}, "
            "dim_labels=b01f_01io->b01f", 2 * 2 * 16 * 8 * (4 * 3) ** 2),
    # its data gradient: the zeros between the 4 elements are not
    # multiplied, so 4 x 3 pairs a dimension and not 8 x 3
    "dgrad": ("bf16[2,8,8,8]", "bf16[2,4,4,16]", "bf16[3,3,8,16]",
              "window={size=3x3 pad=1_2x1_2 lhs_dilate=2x2 "
              "rhs_reversal=1x1}, dim_labels=b01f_01oi->b01f",
              2 * 2 * 8 * 16 * (4 * 3) ** 2),
    # its weight gradient: the output gradient is the window
    "wgrad": ("bf16[3,3,8,16]", "bf16[8,8,8,2]", "bf16[2,4,4,16]",
              "window={size=4x4 pad=1_1x1_1 rhs_dilate=2x2}, "
              "dim_labels=f01b_i01o->01bf", 2 * 8 * 16 * 2 * (3 * 4) ** 2),
    # XLA's TPU form of a 1x1 convolution: the weights as the input, one
    # padded element under a window as large as the image
    "swapped": ("bf16[2,8,8,16]", "bf16[16,8,1,1]", "bf16[2,8,8,8]",
                "window={size=8x8 pad=7_7x7_7 rhs_reversal=1x1}, "
                "dim_labels=bf01_o01i->f01b", 2 * 16 * 2 * 8 * 8 * 8),
    # a matmul as the TPU compiler writes it, the batch as a spatial
    # dimension, no window to slide
    "matmul": ("bf16[4,32,64]", "bf16[4,32,48]", "bf16[64,48,1]",
               "window={size=1}, dim_labels=0bf_oi0->0bf",
               2 * 4 * 32 * 64 * 48),
    "grouped": ("f32[2,8,6]", "f32[2,8,6]", "f32[3,1,6]",
                "window={size=3 pad=2_0}, dim_labels=b0f_0io->b0f, "
                "feature_group_count=6", 2 * 2 * 6 * 1 * 8 * 3),
}


_CONVOLUTIONS["unreadable"] = (
    "bf16[2,4,4,16]", "bf16[2,8,8,8]", "bf16[3,3,8,16]",
    "window={size=3x3}, dim_labels=b01f_01xo->b01f", 0)


@pytest.mark.parametrize("form", sorted(_CONVOLUTIONS))
def test_a_convolution_counts_the_pairs_that_can_meet_an_element(form):
    out, lhs, rhs, attributes, flops = _CONVOLUTIONS[form]
    text = _with(f"  %l.1 = {lhs}{{3,2,1,0}} constant(0)\n"
                 f"  %r.1 = {rhs}{{3,2,1,0:T(8,128)(2,1)}} constant(0)\n"
                 f"  %conv.1 = {out}{{3,2,1,0:T(8,128)(2,1)S(1)}} "
                 f"convolution(%l.1, %r.1), {attributes}")
    record = spmd.program_table(text)["instructions"]["conv.1"]
    assert record["opcode"] == "convolution" and record["flops"] == flops


def test_products_in_nested_fusions_count_once_and_a_while_counts_none():
    """FLOPs and scopes follow a fusion's `calls=` through the fusions
    nested in it; the body of a `while` has rows of its own."""
    body = "jit(mx_train_step)/jvp(net)/while/body/dense1/FullyConnected"
    text = _HLO.replace("%fused_computation.2 (", '''%inner.1 (q0: f32[4,8], q1: f32[8,8]) -> f32[4,8] {
  %q0 = f32[4,8]{1,0} parameter(0)
  %q1 = f32[8,8]{1,0} parameter(1)
  ROOT %dot.8 = f32[4,8]{1,0} dot(f32[4,8]{1,0} %q0, f32[8,8]{1,0} %q1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="''' + body + '''/dot_general"}
}

%outer.1 (r0: f32[4,8], r1: f32[8,8]) -> f32[4,8] {
  %r0 = f32[4,8]{1,0} parameter(0)
  %r1 = f32[8,8]{1,0} parameter(1)
  %fusion.8 = f32[4,8]{1,0} fusion(%r0, %r1), kind=kOutput, calls=%inner.1
  ROOT %neg.1 = f32[4,8]{1,0} negate(%fusion.8), metadata={op_name="jit(mx_train_step)/jvp(net)/act1/Activation/neg"}
}

%body.1 (s0: (f32[4,8], f32[8,8])) -> (f32[4,8], f32[8,8]) {
  %s0 = (f32[4,8]{1,0}, f32[8,8]{1,0}) parameter(0)
  %g0 = f32[4,8]{1,0} get-tuple-element(%s0), index=0
  %g1 = f32[8,8]{1,0} get-tuple-element(%s0), index=1
  %fusion.9 = f32[4,8]{1,0} fusion(%g0, %g1), kind=kOutput, calls=%outer.1
  ROOT %tuple.1 = (f32[4,8]{1,0}, f32[8,8]{1,0}) tuple(%fusion.9, %g1)
}

%fused_computation.2 (''')
    text = _with("  %while.1 = (f32[4,8]{1,0}, f32[8,8]{1,0}) "
                 "while(%fusion.2), condition=%region_0.1, body=%body.1",
                 text)
    table = spmd.program_table(text)
    records = table["instructions"]
    assert records["fusion.9"]["flops"] == 2 * 4 * 8 * 8
    assert records["fusion.9"]["scopes"] == [
        body, "jit(mx_train_step)/jvp(net)/act1/Activation"]
    # `ops` looks for a product in the fusion's own computation only
    # (rule B as PR 24 wrote it), and `pass` follows `ops`
    assert table["ops"]["fusion.9"] == ""
    assert records["fusion.9"]["pass"] == "other"
    assert records["fusion.9"]["passes"] == ["forward"]
    assert records["while.1"]["opcode"] == "while"
    assert records["while.1"]["flops"] == 0
    assert records["while.1"]["scopes"] == []
    # what runs inside a fusion has no row, in either key
    assert "fusion.8" not in records and "dot.8" not in records
    assert sum(r["flops"] for r in records.values()) == 2 * 4 * 8 * 8 + 512


@pytest.mark.parametrize("make", [conv_net, attention_net])
def test_a_step_under_remat_has_recomputed_instructions_and_no_other(make):
    def passes(remat):
        trainer, batch = make(remat=remat)
        trainer.step(*batch)
        program = spmd.step_programs()[-1]
        assert list(program["instructions"]) == list(program["ops"])
        return [r["pass"] for r in program["instructions"].values()]

    plain, mirrored = passes(False), passes(True)
    assert "recomputed" not in plain and "recomputed" in mirrored
    for found in (plain, mirrored):
        assert {"forward", "backward", "update"} <= set(found)
        assert set(found) <= {"forward", "recomputed", "backward",
                              "update", "other"}


def dense_net():
    """Three dense layers under Adam: 32 -> 64 -> 48 -> 10 at batch 16."""
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(nn.Dense(64, activation="relu"),
                nn.Dense(48, activation="relu"), nn.Dense(10))
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    with mx.autograd.pause():
        net(mx.nd.zeros((1, 32), ctx=mx.cpu()))
    trainer = parallel.SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=parallel.make_mesh(dp=1))
    rng = np.random.RandomState(0)
    macs = 16 * (32 * 64 + 64 * 48 + 48 * 10)
    # forward, the weights' gradients, and the data's but the input's
    return trainer, (rng.rand(16, 32).astype("float32"),
                     rng.randint(0, 10, 16).astype(np.int32)), \
        3 * macs - 16 * 32 * 64


def strided_conv_net():
    """Two unpadded 3x3 convolutions of stride 2 (19 -> 9 -> 4) and a
    dense head under SGD at batch 8."""
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, strides=2, layout="NHWC",
                          activation="relu"),
                nn.Conv2D(16, 3, strides=2, layout="NHWC",
                          activation="relu"),
                nn.GlobalAvgPool2D(layout="NHWC"), nn.Dense(4))
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    with mx.autograd.pause():
        net(mx.nd.zeros((1, 19, 19, 3), ctx=mx.cpu()))
    trainer = parallel.SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=parallel.make_mesh(dp=1))
    rng = np.random.RandomState(0)
    conv0, conv1 = 8 * 9 * 9 * 8 * 27, 8 * 4 * 4 * 16 * 72
    return trainer, (rng.rand(8, 19, 19, 3).astype("float32"),
                     rng.randint(0, 4, 8).astype(np.int32)), \
        3 * (conv0 + conv1 + 8 * 16 * 4) - conv0


@pytest.mark.parametrize("make", [dense_net, strided_conv_net])
def test_the_tables_flops_are_the_layers_products_and_a_floor(make):
    trainer, batch, macs = make()
    trainer.step(*batch)
    records = spmd.step_programs()[-1]["instructions"]
    flops = sum(r["flops"] for r in records.values())
    assert abs(flops - 2 * macs) <= 0.02 * 2 * macs
    cost = trainer.step_executable().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert flops <= cost["flops"]
    # products are forward or backward work, never the update's own
    assert all(r["pass"] != "update" for r in records.values()
               if r["flops"])


def test_layout_gauges_are_set_once_a_trainer_with_telemetry_on():
    from mxnet_tpu.telemetry import instruments as ins

    trainer, batch = conv_net()
    trainer.step(*batch).asnumpy()      # telemetry off: nothing is set
    assert trainer._layout_published is False
    tracing.enable()
    try:
        trainer.step(*batch).asnumpy()
        assert trainer._layout_published is True
        assert ins.step_layout_axis_size("dp").value == 1
        assert ins.step_state_shard_factor().value == 1
        # a trainer's layout never changes: a later step leaves the
        # gauges to whoever set them last
        ins.step_state_shard_factor().set(7)
        trainer.step(*batch).asnumpy()
        assert ins.step_state_shard_factor().value == 7
    finally:
        tracing.disable()


# first-step losses of the two seeded nets before any scope existed
# (float32 on this CPU backend): a scope is metadata and changes no
# arithmetic.  The convolution net's is a225b38's; the attention net's is
# PR 26's, whose dropout mask on the attention probabilities comes from
# a hash and no longer from threefry (1.447582721710205 before), moved
# once more in PR 52, when the hidden dropout's masks followed it to the
# hash (1.7128827571868896 before)
@pytest.mark.parametrize("make, parent_loss", [
    (conv_net, 1.3157033920288086), (attention_net, 1.5062334537506104)])
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
    assert spmd.step_programs()[-1]["instructions"] is first["instructions"]
    assert list(first["instructions"]) == list(first["ops"])
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
