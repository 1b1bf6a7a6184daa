"""Opt-in REAL-DEVICE test suite: op consistency cpu vs tpu + model
forward/backward + a converging train step on the actual chip.

Counterpart of the reference's tests/python/gpu/test_operator_gpu.py
(same-computation-two-devices consistency via check_consistency).

Run via:  python tools/run_tpu_tests.py
(sets MXNET_TEST_PLATFORM=tpu so conftest keeps the accelerator visible,
executes this module on the chip, and writes the TPU_TESTS_r*.json
artifact with pass counts).  Skipped in the normal CPU suite.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal, check_consistency

pytestmark = pytest.mark.skipif(
    os.environ.get("MXNET_TEST_PLATFORM") != "tpu",
    reason="on-device suite; run via tools/run_tpu_tests.py")


def _ctxs():
    return [mx.cpu(0), mx.tpu(0)]


def _r(*shape):
    return np.random.RandomState(0).randn(*shape).astype("float32")


# matmul-family ops run on the MXU in bf16 by default (jax 'default'
# precision — the perf-correct choice this framework makes, like the
# reference's TensorCore fp16 lane); consistency vs fp32 CPU uses the
# correspondingly looser tolerance, exactly as the reference's fp16 GPU
# tests do (ref: test_operator_gpu.py check_consistency tol tables).
MXU_CASES = [
    ("FullyConnected",
     lambda x, w, b: nd.FullyConnected(x, w, b, num_hidden=8),
     [_r(4, 16), _r(8, 16), _r(8)]),
    ("Convolution",
     lambda x, w, b: nd.Convolution(x, w, b, kernel=(3, 3), num_filter=8,
                                    pad=(1, 1)),
     [_r(2, 3, 8, 8), _r(8, 3, 3, 3), _r(8)]),
    ("dot", lambda a, b: nd.dot(a, b), [_r(4, 8), _r(8, 6)]),
    ("linalg_gemm2", lambda a, b: nd.linalg_gemm2(a, b),
     [_r(3, 4), _r(4, 5)]),
]


@pytest.mark.parametrize("name,fn,args", MXU_CASES,
                         ids=[c[0] for c in MXU_CASES])
def test_op_consistency_mxu(name, fn, args):
    check_consistency(fn, _ctxs(), args, rtol=3e-2, atol=3e-2)


OP_CASES = [
    ("Pooling",
     lambda x: nd.Pooling(x, kernel=(2, 2), stride=(2, 2),
                          pool_type="max"),
     [_r(2, 3, 8, 8)]),
    ("Activation-relu", lambda x: nd.Activation(x, act_type="relu"),
     [_r(4, 32)]),
    ("softmax", lambda x: nd.softmax(x), [_r(4, 10)]),
    ("LayerNorm",
     lambda x, g, b: nd.LayerNorm(x, g, b), [_r(4, 16), _r(16), _r(16)]),
    ("broadcast_add", lambda a, b: a + b, [_r(4, 8), _r(1, 8)]),
    ("sum", lambda x: nd.sum(x, axis=1), [_r(4, 9)]),
    ("mean", lambda x: nd.mean(x, axis=0), [_r(5, 7)]),
    ("exp-log", lambda x: nd.log(nd.exp(x) + 1.0), [_r(4, 6)]),
    ("transpose-reshape",
     lambda x: nd.reshape(nd.transpose(x, axes=(0, 2, 1)), shape=(2, -1)),
     [_r(2, 3, 4)]),
    ("concat", lambda a, b: nd.concat(a, b, dim=1), [_r(3, 4), _r(3, 5)]),
    ("take",
     lambda x: nd.take(x, nd.array(np.array([0, 2], "f4"), ctx=x.ctx),
                       axis=0),
     [_r(4, 5)]),
    ("sigmoid-tanh", lambda x: nd.sigmoid(x) * nd.tanh(x), [_r(4, 4)]),
    ("L2Normalization", lambda x: nd.L2Normalization(x), [_r(4, 8)]),
    ("smooth_l1", lambda x: nd.smooth_l1(x, scalar=1.0), [_r(4, 8)]),
]


@pytest.mark.parametrize("name,fn,args", OP_CASES,
                         ids=[c[0] for c in OP_CASES])
def test_op_consistency_cpu_tpu(name, fn, args):
    check_consistency(fn, _ctxs(), args, rtol=2e-3, atol=2e-3)


NOGRAD_CASES = [
    ("topk", lambda x: nd.topk(x, k=3, ret_typ="value"), [_r(4, 10)]),
    ("argmax", lambda x: nd.argmax(x, axis=1), [_r(4, 10)]),
    ("MultiBoxPrior",
     lambda x: nd.MultiBoxPrior(x, sizes=(0.5, 0.2), ratios=(1, 2)),
     [_r(1, 3, 4, 4)]),
    ("box_nms",
     lambda x: nd.box_nms(x, overlap_thresh=0.5, force_suppress=True),
     [np.abs(_r(12, 6))]),
    ("quantize-dequantize",
     lambda x: nd.dequantize(*nd.quantize_v2(x, out_type="int8")),
     [_r(6, 6)]),
]


@pytest.mark.parametrize("name,fn,args", NOGRAD_CASES,
                         ids=[c[0] for c in NOGRAD_CASES])
def test_op_consistency_nograd(name, fn, args):
    check_consistency(fn, _ctxs(), args, rtol=2e-3, atol=2e-3, grad=False)


def test_batchnorm_train_consistency():
    def f(x, g, b):
        mm = nd.zeros(5, ctx=x.ctx)
        mv = nd.ones(5, ctx=x.ctx)
        return nd.BatchNorm(x, g, b, mm, mv)

    check_consistency(f, _ctxs(), [_r(4, 5, 6, 6), _r(5), _r(5)],
                      rtol=5e-3, atol=5e-3)


def test_resnet_block_fwd_bwd_on_chip():
    """A residual conv block end-to-end on the TPU: finite loss + grads."""
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.resnet18_v1(classes=10)
    net.initialize(mx.initializer.Xavier(), ctx=mx.tpu(0))
    x = nd.array(_r(2, 3, 32, 32), ctx=mx.tpu(0))
    y = nd.array(np.array([1, 3], "f4"), ctx=mx.tpu(0))
    from mxnet_tpu.gluon import loss as gloss

    params = [p for _, p in sorted(net.collect_params().items())]
    with mx.autograd.record():
        out = net(x)
        loss = gloss.SoftmaxCrossEntropyLoss()(out, y).mean()
    loss.backward()
    assert np.isfinite(float(loss.asnumpy()))
    gnorm = sum(float((p.grad().asnumpy() ** 2).sum()) for p in params
                if p.grad_req != "null")
    assert np.isfinite(gnorm) and gnorm > 0


def test_train_step_converges_on_chip():
    """SPMD train step on the real chip drives the loss down."""
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import loss as gloss

    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(32, activation="relu"),
            mx.gluon.nn.Dense(4))
    net.initialize(ctx=mx.cpu())
    net(nd.zeros((2, 8), ctx=mx.cpu()))
    rng = np.random.RandomState(0)
    x = rng.randn(64, 8).astype("f4")
    y = (rng.rand(64) * 4).astype(np.int32)
    with parallel.make_mesh(dp=1):
        tr = parallel.SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                                  "sgd", {"learning_rate": 0.5})
        losses = [float(tr.step(x, y).asnumpy()) for _ in range(60)]
    assert losses[-1] < losses[0] * 0.7, losses[::8]


def test_fused_conv_bwd_pallas_vs_xla_on_chip():
    """The single-pass fused BACKWARD kernel (MXNET_FUSED_CONVBN_BWD)
    vs the XLA linear_transpose backward on the real chip — every
    gradient, Mosaic-compiled (the CPU suite covers interpret only)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_convbn as pcb

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(4, 16, 16, 128).astype("float32") * 0.5,
                    jnp.bfloat16)
    w = jnp.asarray(rng.randn(128, 128, 3, 3).astype("float32") * 0.05,
                    jnp.bfloat16)
    sc = jnp.asarray(rng.rand(128).astype("float32") + 0.5)
    bi = jnp.asarray(rng.randn(128).astype("float32") * 0.1)
    sh = jnp.asarray(rng.randn(128).astype("float32") * 0.1)
    y = jnp.asarray(rng.randn(4, 16, 16, 128).astype("float32") * 0.5,
                    jnp.bfloat16)
    gy = jnp.asarray(rng.randn(4, 16, 16, 128).astype("float32") * 0.1,
                     jnp.bfloat16)
    gs1 = jnp.asarray(rng.randn(128).astype("float32") * 1e-3)
    gs2 = jnp.asarray(rng.rand(128).astype("float32") * 1e-3)
    kw = dict(kernel=(3, 3), stride=(1, 1), pad=(1, 1), act_in=True,
              want_stats=True)
    gx_p, dw_p, gsc_p, gbi_p = pcb._pallas_unit_bwd(
        x, w, sc, bi, sh, y, gy, gs1, gs2, **kw)
    # XLA oracle: same math through the fallback backward (knob forced
    # off so the oracle cannot itself take the Pallas path)
    res = (x, w, sc, bi, sh, y)
    old = os.environ.pop("MXNET_FUSED_CONVBN_BWD", None)
    try:
        gx_x, dw_x, gsc_x, gbi_x, _ = pcb._unit_bwd(
            (3, 3), (1, 1), (1, 1), True, True, res, (gy, gs1, gs2))
    finally:
        if old is not None:
            os.environ["MXNET_FUSED_CONVBN_BWD"] = old
    assert_almost_equal(np.asarray(gx_p, np.float32),
                        np.asarray(gx_x, np.float32), rtol=3e-2,
                        atol=3e-2)
    assert_almost_equal(np.asarray(dw_p, np.float32),
                        np.asarray(dw_x, np.float32), rtol=3e-2,
                        atol=3e-2)
    assert_almost_equal(np.asarray(gsc_p), np.asarray(gsc_x), rtol=3e-2,
                        atol=3e-1)
    assert_almost_equal(np.asarray(gbi_p), np.asarray(gbi_x), rtol=3e-2,
                        atol=3e-1)


def test_fused_conv_unit_pallas_vs_xla_on_chip():
    """The fused Conv+BN+ReLU unit's PALLAS kernel vs its XLA fallback
    on the real chip: same outputs and statistics (the CPU suite can
    only check interpret mode — this is the Mosaic-compiled kernel)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_convbn as pcb

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 16, 16, 128).astype("float32") * 0.5,
                    jnp.bfloat16)
    w = jnp.asarray(rng.randn(128, 128, 3, 3).astype("float32") * 0.05,
                    jnp.bfloat16)
    sc = jnp.asarray(rng.rand(128).astype("float32") + 0.5)
    bi = jnp.asarray(rng.randn(128).astype("float32") * 0.1)
    sh = jnp.asarray(rng.randn(128).astype("float32") * 0.1)
    kw = dict(kernel=(3, 3), stride=(1, 1), pad=(1, 1), act_in=True,
              want_stats=True)
    y_p, s1_p, s2_p = pcb._pallas_unit(x, w, sc, bi, sh, **kw)
    y_x, s1_x, s2_x = pcb._xla_unit(x, w, sc, bi, sh, **kw)
    assert_almost_equal(np.asarray(y_p, np.float32),
                        np.asarray(y_x, np.float32), rtol=2e-2, atol=2e-2)
    n = y_p.size // y_p.shape[-1]
    assert_almost_equal(np.asarray(s1_p) / n, np.asarray(s1_x) / n,
                        rtol=2e-2, atol=2e-2)
    assert_almost_equal(np.asarray(s2_p) / n, np.asarray(s2_x) / n,
                        rtol=3e-2, atol=3e-2)


def test_fused_resnet_block_matches_on_chip():
    """Whole fused bottleneck (Pallas path live) vs the op-granular
    block on the chip: train-mode forward + every gradient."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1

    rng = np.random.RandomState(1)
    xnp = rng.randn(2, 8, 8, 16).astype("float32")
    block = BottleneckV1(16, 1, downsample=False, in_channels=16,
                         layout="NHWC")
    # params on the CHIP: eager inputs default to tpu(0) on this host,
    # and cpu-resident params would raise a ctx mismatch (and the test
    # exists to run the Pallas path on the device anyway)
    block.initialize(mx.initializer.Xavier(), ctx=mx.tpu(0))
    block(mx.nd.array(xnp))
    snap = {n_: p.data().asnumpy().copy()
            for n_, p in block.collect_params().items()}

    def run(fused):
        for n_, p in block.collect_params().items():
            p.set_data(mx.nd.array(snap[n_]))
        block.hybridize()
        if fused:
            os.environ["MXNET_FUSED_CONVBN"] = "1"
        try:
            with autograd.record():
                out = block(mx.nd.array(xnp))
                loss = (out * out).sum()
            loss.backward()
        finally:
            os.environ.pop("MXNET_FUSED_CONVBN", None)
        grads = {n_: p.grad().asnumpy().copy()
                 for n_, p in block.collect_params().items()
                 if p.grad_req != "null"}
        return out.asnumpy(), grads

    out_r, g_r = run(False)
    out_f, g_f = run(True)
    assert_almost_equal(out_f, out_r, rtol=1e-3, atol=1e-3)
    for n_ in g_r:
        assert_almost_equal(g_f[n_], g_r[n_], rtol=5e-3, atol=5e-3)


def test_pallas_attention_vs_xla_on_chip(monkeypatch):
    """Flash-attention Pallas kernel vs the XLA reference on-chip."""
    from mxnet_tpu.ops import registry as reg

    rng = np.random.RandomState(2)
    b, s, d = 2, 128, 64
    q = nd.array(rng.randn(b, s, d).astype("float32") * 0.2)
    k = nd.array(rng.randn(b, s, d).astype("float32") * 0.2)
    v = nd.array(rng.randn(b, s, d).astype("float32") * 0.2)
    mask = nd.array(np.ones((b, s), "float32"))
    out_p = nd.dot_product_attention(q, k, v, mask, num_heads=1)
    # The reference for the second call, by the explicit opt-out.  The
    # first call jit-compiled the op with the kernel baked in and an
    # identical-shape call would hit the registry's jit cache, so clear
    # the op-level jit caches to force a retrace (one process per chip:
    # a subprocess could not take it).
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    saved_jit = dict(reg._jit_cache)
    saved_grad = dict(reg._grad_cache)
    reg._jit_cache.clear()
    reg._grad_cache.clear()
    try:
        out_x = nd.dot_product_attention(q, k, v, mask, num_heads=1)
    finally:
        reg._jit_cache.update(saved_jit)
        reg._grad_cache.update(saved_grad)
    assert_almost_equal(out_p.asnumpy(), out_x.asnumpy(), rtol=2e-2,
                        atol=2e-2)


def test_deploy_artifact_serves_on_chip(tmp_path):
    """The multi-platform deployment promise on real hardware: export a
    model (lowered for cpu AND tpu), serve it on the TPU backend, and
    match a float32 numpy oracle computed from the same weights."""
    from mxnet_tpu.contrib import deploy
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8))
        net.add(nn.Dense(4, in_units=16))
    net.initialize(mx.initializer.Xavier(), ctx=mx.tpu(0))
    x_np = np.random.RandomState(0).rand(4, 8).astype("float32")
    deploy.export_model(net, str(tmp_path), [mx.nd.array(x_np)])
    served = deploy.import_model(str(tmp_path))
    got = served(mx.nd.array(x_np))
    assert got.ctx.device_type == "tpu"
    # numpy oracle from the exported weights
    p = {n_: v.asnumpy() for n_, v in
         ((n_, pp.data()) for n_, pp in net.collect_params().items())}
    names = sorted(p)
    w0 = p[[n_ for n_ in names if n_.endswith("dense0_weight")][0]]
    b0 = p[[n_ for n_ in names if n_.endswith("dense0_bias")][0]]
    w1 = p[[n_ for n_ in names if n_.endswith("dense1_weight")][0]]
    b1 = p[[n_ for n_ in names if n_.endswith("dense1_bias")][0]]
    h = np.maximum(x_np @ w0.T + b0, 0.0)
    ref = h @ w1.T + b1
    # the Dense layers run on the MXU in bf16 by default: the MXU_CASES
    # tolerance, not float32's (8e-3 relative measured on the v5e)
    assert_almost_equal(got.asnumpy(), ref, rtol=3e-2, atol=3e-2)
