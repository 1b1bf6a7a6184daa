"""Portable StableHLO deployment artifacts (contrib/deploy.py).

The deployment claim is 'runs without the model's Python code', so the
central test reloads the artifact in a SUBPROCESS that never imports
the model class — the reference's C++-predictor story
(ref: docs/faq/smart_device.md) re-expressed as versioned StableHLO.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.contrib import deploy
from mxnet_tpu.gluon import nn

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8))
        net.add(nn.Dense(4, in_units=16))
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    return net


def test_roundtrip_same_process(tmp_path):
    net = _mlp()
    x = nd.array(np.random.RandomState(0).rand(2, 8).astype("float32"))
    ref = net(x).asnumpy()
    deploy.export_model(net, str(tmp_path), [x])
    served = deploy.import_model(str(tmp_path))
    np.testing.assert_allclose(served(x).asnumpy(), ref, rtol=1e-6)
    # artifact layout is the documented one
    assert sorted(os.listdir(tmp_path)) == [
        "meta.json", "model.params", "model.stablehlo"]


def test_reload_in_subprocess_without_model_code(tmp_path):
    net = _mlp()
    rng = np.random.RandomState(1)
    x = nd.array(rng.rand(2, 8).astype("float32"))
    ref = net(x).asnumpy()
    deploy.export_model(net, str(tmp_path), [x])
    np.save(tmp_path / "x.npy", x.asnumpy())
    np.save(tmp_path / "ref.npy", ref)
    script = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "os.environ.get('XLA_FLAGS','')\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "from mxnet_tpu.contrib import deploy\n"
        f"served = deploy.import_model({str(tmp_path)!r})\n"
        f"x = np.load({str(tmp_path / 'x.npy')!r})\n"
        f"ref = np.load({str(tmp_path / 'ref.npy')!r})\n"
        "got = served(x).asnumpy()\n"
        "np.testing.assert_allclose(got, ref, rtol=1e-6)\n"
        "print('SUBPROCESS_SERVE_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", script], env=env, cwd=_REPO,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, (p.stdout + p.stderr)[-1500:]
    assert "SUBPROCESS_SERVE_OK" in p.stdout


def test_param_swap_changes_output(tmp_path):
    net = _mlp()
    x = nd.array(np.random.RandomState(2).rand(2, 8).astype("float32"))
    deploy.export_model(net, str(tmp_path), [x])
    served = deploy.import_model(str(tmp_path))
    before = served(x).asnumpy()
    # 'further training': scale one WEIGHT (biases start at zero, where
    # scaling is a no-op), swap the whole set in
    params = {n: p.data() for n, p in sorted(net.collect_params().items())}
    wname = next(n for n in sorted(params) if n.endswith("weight"))
    params[wname] = params[wname] * 2.0
    served.set_params(params)
    after = served(x).asnumpy()
    assert not np.allclose(after, before)


def test_shape_and_arity_validation(tmp_path):
    net = _mlp()
    x = nd.array(np.zeros((2, 8), "float32"))
    deploy.export_model(net, str(tmp_path), [x])
    served = deploy.import_model(str(tmp_path))
    with pytest.raises(MXNetError, match="fixed-shape"):
        served(nd.array(np.zeros((3, 8), "float32")))
    with pytest.raises(MXNetError, match="takes 1 inputs"):
        served(x, x)
    # a non-artifact directory is rejected up front
    (tmp_path / "empty").mkdir()
    (tmp_path / "empty" / "meta.json").write_text(json.dumps({}))
    with pytest.raises(MXNetError, match="not a deploy artifact"):
        deploy.import_model(str(tmp_path / "empty"))


def test_resnet_block_export(tmp_path):
    """A conv/BN model exports too (running stats are parameters of the
    eval-mode program like any other)."""
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BasicBlockV1

    net = BasicBlockV1(8, 1, downsample=False, in_channels=8,
                       layout="NHWC")
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    x = nd.array(np.random.RandomState(3).rand(1, 8, 8, 8)
                 .astype("float32"))
    net(x)  # resolve shapes
    ref = net(x).asnumpy()
    deploy.export_model(net, str(tmp_path), [x])
    served = deploy.import_model(str(tmp_path))
    np.testing.assert_allclose(served(x).asnumpy(), ref, rtol=1e-5,
                               atol=1e-6)


def test_deferred_init_resolved_by_export(tmp_path):
    """export_model holds example inputs, so it resolves deferred
    shapes itself (the CachedOp resolve-and-retry pattern)."""
    net = nn.Dense(4)  # no in_units
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    x = nd.array(np.random.RandomState(5).rand(2, 6).astype("float32"))
    deploy.export_model(net, str(tmp_path), [x])
    served = deploy.import_model(str(tmp_path))
    np.testing.assert_allclose(served(x).asnumpy(), net(x).asnumpy(),
                               rtol=1e-6)


def test_bad_param_swap_rejected_atomically(tmp_path):
    net = _mlp()
    x = nd.array(np.zeros((2, 8), "float32"))
    deploy.export_model(net, str(tmp_path), [x])
    served = deploy.import_model(str(tmp_path))
    good = served(x).asnumpy()
    params = {n: p.data() for n, p in sorted(net.collect_params().items())}
    wname = next(n for n in sorted(params) if n.endswith("weight"))
    bad = dict(params)
    bad[wname] = nd.zeros((3, 3))
    with pytest.raises(MXNetError, match="shape"):
        served.set_params(bad)
    # the failed swap must not have clobbered the working weights
    np.testing.assert_allclose(served(x).asnumpy(), good, rtol=0, atol=0)
    bad[wname] = nd.zeros(params[wname].shape, dtype="int32")
    with pytest.raises(MXNetError, match="dtype"):
        served.set_params(bad)


def test_input_dtype_validated(tmp_path):
    net = _mlp()
    x = nd.array(np.zeros((2, 8), "float32"))
    deploy.export_model(net, str(tmp_path), [x])
    served = deploy.import_model(str(tmp_path))
    with pytest.raises(MXNetError, match="dtype"):
        served(np.zeros((2, 8), "int32"))


def test_output_ctx_follows_input(tmp_path):
    net = _mlp()
    x = nd.array(np.zeros((2, 8), "float32"))
    deploy.export_model(net, str(tmp_path), [x])
    served = deploy.import_model(str(tmp_path))
    assert served(x).ctx == x.ctx


def test_dynamic_batch_export(tmp_path):
    """dynamic_batch=True serves any batch size from one artifact (the
    serving analogue of BucketingModule), including in a fresh process."""
    net = _mlp()
    x8 = nd.array(np.random.RandomState(7).rand(8, 8).astype("float32"))
    deploy.export_model(net, str(tmp_path), [x8], dynamic_batch=True)
    served = deploy.import_model(str(tmp_path))
    for n in (1, 3, 32):
        xn = nd.array(np.random.RandomState(n).rand(n, 8)
                      .astype("float32"))
        got = served(xn).asnumpy()
        np.testing.assert_allclose(got, net(xn).asnumpy(), rtol=1e-6)
    # non-batch dims stay fixed
    with pytest.raises(MXNetError, match="free batch dim"):
        served(nd.array(np.zeros((2, 9), "float32")))


def test_output_pytree_structure_preserved(tmp_path):
    """A block returning a nested dict/tuple serves the SAME structure,
    not a flat list in tree-flatten order."""
    from mxnet_tpu.gluon.block import HybridBlock

    class _Multi(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.d = nn.Dense(4, in_units=8)

        def hybrid_forward(self, F, x):
            y = self.d(x)
            return {"logits": y, "extras": (y * 2, y + 1)}

    net = _Multi()
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    x = nd.array(np.random.RandomState(9).rand(2, 8).astype("float32"))
    ref = net(x)
    deploy.export_model(net, str(tmp_path), [x])
    served = deploy.import_model(str(tmp_path))
    got = served(x)
    assert isinstance(got, dict) and set(got) == {"logits", "extras"}
    assert isinstance(got["extras"], tuple) and len(got["extras"]) == 2
    np.testing.assert_allclose(got["logits"].asnumpy(),
                               ref["logits"].asnumpy(), rtol=1e-6)
    np.testing.assert_allclose(got["extras"][0].asnumpy(),
                               ref["extras"][0].asnumpy(), rtol=1e-6)
    np.testing.assert_allclose(got["extras"][1].asnumpy(),
                               ref["extras"][1].asnumpy(), rtol=1e-6)


def test_output_namedtuple_fields_preserved(tmp_path):
    """A block returning a namedtuple serves a NAMEDTUPLE back — field
    access by name must survive the artifact round-trip (a plain-tuple
    encoding would break consumers silently)."""
    import collections

    from mxnet_tpu.gluon.block import HybridBlock

    Out = collections.namedtuple("Out", ["logits", "hidden"])

    class _NT(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.d = nn.Dense(4, in_units=8)

        def hybrid_forward(self, F, x):
            y = self.d(x)
            return Out(logits=y, hidden=y * 2)

    net = _NT()
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    x = nd.array(np.random.RandomState(21).rand(2, 8).astype("float32"))
    ref = net(x)
    deploy.export_model(net, str(tmp_path), [x])
    with open(tmp_path / "meta.json") as f:
        tree = json.load(f)["out_tree"]
    assert tree["kind"] == "namedtuple"
    assert tree["fields"] == ["logits", "hidden"]
    served = deploy.import_model(str(tmp_path))
    got = served(x)
    assert hasattr(got, "_fields") and got._fields == ("logits", "hidden")
    np.testing.assert_allclose(got.logits.asnumpy(),
                               ref.logits.asnumpy(), rtol=1e-6)
    np.testing.assert_allclose(got.hidden.asnumpy(),
                               ref.hidden.asnumpy(), rtol=1e-6)


def test_meta_records_exporting_jax_version(tmp_path):
    """meta.json carries the exporter's jax version so a later-era
    deserialization failure is attributable (nightly compat test)."""
    import jax

    net = _mlp()
    x = nd.array(np.zeros((2, 8), "float32"))
    deploy.export_model(net, str(tmp_path), [x])
    with open(tmp_path / "meta.json") as f:
        assert json.load(f)["jax_version"] == jax.__version__


def test_dynamic_batch_scalar_side_input(tmp_path):
    """0-d side-inputs stay concrete under dynamic_batch instead of
    being fabricated into (b,) vectors."""
    from mxnet_tpu.gluon.block import HybridBlock

    class _Scaled(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.d = nn.Dense(4, in_units=8)

        def hybrid_forward(self, F, x, s):
            return self.d(x) * s

    net = _Scaled()
    net.initialize(mx.initializer.Xavier(), ctx=mx.cpu())
    x = nd.array(np.random.RandomState(11).rand(2, 8).astype("float32"))
    s = nd.array(np.float32(2.0))
    deploy.export_model(net, str(tmp_path), [x, s], dynamic_batch=True)
    served = deploy.import_model(str(tmp_path))
    x5 = nd.array(np.random.RandomState(12).rand(5, 8).astype("float32"))
    np.testing.assert_allclose(served(x5, s).asnumpy(),
                               net(x5, s).asnumpy(), rtol=1e-6)


def test_artifact_is_multi_platform(tmp_path):
    """Artifacts are lowered for BOTH cpu and tpu, so a model exported
    on the dev box serves on the accelerator host (jax.export would
    otherwise pin the lowering platform)."""
    net = _mlp()
    x = nd.array(np.zeros((2, 8), "float32"))
    deploy.export_model(net, str(tmp_path), [x])
    with open(tmp_path / "meta.json") as f:
        meta = json.load(f)
    assert sorted(meta["platforms"]) == ["cpu", "tpu"]
    from jax import export as jexport

    with open(tmp_path / "model.stablehlo", "rb") as f:
        exported = jexport.deserialize(f.read())
    assert sorted(exported.platforms) == ["cpu", "tpu"]


def test_single_platform_opt_out(tmp_path):
    net = _mlp()
    x = nd.array(np.zeros((2, 8), "float32"))
    deploy.export_model(net, str(tmp_path), [x], platforms=("cpu",))
    with open(tmp_path / "meta.json") as f:
        meta = json.load(f)
    assert meta["platforms"] == ["cpu"]
    served = deploy.import_model(str(tmp_path))
    assert served(x).shape == (2, 4)


def test_non_platform_export_error_not_retried(tmp_path, monkeypatch):
    """An export failure unrelated to platform lowering re-raises
    directly instead of burning a second trace on the fallback."""
    from jax import export as jexport

    calls = {"n": 0}
    real = jexport.export

    def spy(*a, **k):
        calls["n"] += 1
        if "platforms" in k:
            raise ValueError("symbolic dimension mismatch in reshape")
        return real(*a, **k)

    monkeypatch.setattr("jax.export.export", spy)
    net = _mlp()
    x = nd.array(np.zeros((2, 8), "float32"))
    with pytest.raises(ValueError, match="symbolic dimension"):
        deploy.export_model(net, str(tmp_path), [x])
    assert calls["n"] == 1  # no second lowering attempt


def test_unknown_platform_raises_not_degrades(tmp_path):
    """A typo'd platform name raises up front (jax.export would accept
    the string silently and produce an artifact that can never serve
    where it claims to)."""
    net = _mlp()
    x = nd.array(np.zeros((2, 8), "float32"))
    with pytest.raises(MXNetError, match="gpux"):
        deploy.export_model(net, str(tmp_path), [x],
                            platforms=("cpu", "gpux"))
    assert not (tmp_path / "model.stablehlo").exists()
