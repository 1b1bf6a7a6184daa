"""The bug class that cost the last chip window: on a TPU host the default
context is tpu(0), every model builder initialises on cpu(), and an eager
warm pass that builds its input on the DEFAULT context dies with
"Parameter ... was not initialized on context tpu(0); it lives on
[cpu(0)]".  The CPU backend cannot show that while cpu(0) is also the
default, so these tests make the virtual CPU devices 1..7 the
"accelerators": tpu(0) is then a different device from cpu(0), as on the
chip, and the smoke's builders and every bench_all.py configuration run
one step at toy size under it."""
import argparse

import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import context


@pytest.fixture
def accelerator_default(monkeypatch):
    monkeypatch.setattr(context, "_accelerator_devices",
                        lambda: jax.local_devices(backend="cpu")[1:])
    assert context.current_context() == mx.tpu(0)
    assert mx.tpu(0).jax_device != mx.cpu(0).jax_device
    assert mx.nd.zeros((1,)).ctx == mx.tpu(0)


# the two ResNet-50-backbone steps cost ~20 s of compile each; nightly
_slow = pytest.mark.slow


@pytest.mark.parametrize("config", [
    "mnist_mlp", "bert_base", "transformer_nmt",
    pytest.param("resnet50", marks=_slow),
    pytest.param("ssd_resnet50", marks=_slow)])
def test_bench_all_builders_under_accelerator_default(
        accelerator_default, config):
    import bench_all

    args = argparse.Namespace(cpu_smoke=True, steps=1, warmup=1,
                              config=config)
    row = bench_all.CONFIGS[config](args)
    assert row["value"] > 0


def test_smoke_builders_under_accelerator_default(accelerator_default):
    import chip_smoke
    from mxnet_tpu.compile_cache import jax_cache

    cache = jax_cache.JaxCache("unused")
    chip_smoke.resnet_phase(cache, batch=2, image=32, warmup=1, steps=1,
                            model="resnet18_v1", classes=10)
    out = chip_smoke.gluon_phase(mx.tpu(0), batch=16, steps=3)
    assert out["ctx"] == "tpu(0)"


def test_deploy_artifact_serves_under_accelerator_default(
        accelerator_default, tmp_path):
    """The on-chip lane's one default-path failure of PR 21: a weights
    file loads onto cpu(), the inputs arrive on the default context."""
    import numpy as np

    from mxnet_tpu.contrib import deploy
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=8))
    net.initialize(ctx=mx.tpu(0))
    x = mx.nd.array(np.ones((2, 8), "float32"))
    deploy.export_model(net, str(tmp_path), [x])
    got = deploy.import_model(str(tmp_path))(x)
    assert got.ctx == mx.tpu(0)
    np.testing.assert_allclose(got.asnumpy(), net(x).asnumpy(), rtol=1e-5)


def test_spmd_trainer_forward_binds_self():
    """SPMDTrainer.forward closed over an undefined name `trainer`."""
    import numpy as np

    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon import nn

    net = nn.Dense(3, in_units=4)
    net.initialize(ctx=mx.cpu())
    with parallel.make_mesh(dp=1):
        trainer = parallel.SPMDTrainer(net, gloss.L2Loss(), "sgd",
                                       {"learning_rate": 0.1})
    out = trainer.forward(np.ones((2, 4), np.float32))
    assert out.shape == (2, 3)
