"""The op `Dropout` draws its mask from the repo's one generator (PR 52):
`ops/dropout_mask.py`'s integer hash of (the site's key words, the
element's GLOBAL index), the one attention's dropout has used since PR 26.
A pure function of the index, so the same bits under `jit`, eagerly, under
GSPMD and from numpy, which is what lets these tests hold the op's output
AND gradient to `where(mask, x * (1 / keep), 0)` under the identical mask.
"""
import math

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import parallel, telemetry
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.block import HybridBlock
from mxnet_tpu.gluon.model_zoo.bert import BERTEncoderCell
from mxnet_tpu.ops import dropout_mask as dm
from mxnet_tpu.ops import nn as ops_nn
from mxnet_tpu.parallel import spmd

_NP = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}
_SHAPES = {2: (6, 10), 3: (3, 5, 8), 4: (2, 3, 4, 6)}


def _twin(x, key, p, axes=()):
    """`where(mask, x * (1 / keep), 0)` in numpy, in x's dtype, the mask
    from the generator's numpy side under the identical key."""
    shape = [1 if d in axes else n for d, n in enumerate(x.shape)]
    mask = dm.keep_mask(np.asarray(dm._key_words(key)), shape, 1.0 - p,
                        xp=np)
    scale = np.asarray(1.0 / (1.0 - p), x.dtype)
    return np.where(mask, x * scale, np.zeros((), x.dtype)), mask


def _data(shape, dtype, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(_NP[dtype])


@pytest.mark.parametrize("p", [0.1, 0.5, 0.9])
@pytest.mark.parametrize("axes", [(), (0,), (1,)])
@pytest.mark.parametrize("rank", [2, 3, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_output_and_gradient_equal_the_numpy_twin_exactly(dtype, rank, axes,
                                                          p):
    x, g = _data(_SHAPES[rank], dtype), _data(_SHAPES[rank], dtype, seed=1)
    key = jax.random.PRNGKey(7 + rank)
    out, vjp = jax.vjp(lambda d: ops_nn._dropout(
        d, key, p=p, axes=axes, _train=True), jnp.asarray(x))
    (dx,) = vjp(jnp.asarray(g))
    want, mask = _twin(x, key, p, axes)
    assert out.dtype == dx.dtype == jnp.dtype(dtype)
    assert mask.shape == tuple(1 if d in axes else n
                               for d, n in enumerate(x.shape))
    np.testing.assert_array_equal(np.asarray(out), want)
    np.testing.assert_array_equal(np.asarray(dx), _twin(g, key, p, axes)[0])


def _traced(how, fn, x):
    if how == "eager":
        return fn(x)
    if how == "jit":
        return jax.jit(fn)(x)
    mesh = parallel.make_mesh(dp=4, devices=jax.devices()[:4])
    rows = NamedSharding(mesh.mesh, P("dp"))

    def inside(x):
        with mesh:
            return fn(x)
    out = jax.jit(inside, in_shardings=rows, out_shardings=rows)(
        jax.device_put(x, rows))
    assert all(len(o.sharding.device_set) == 4 for o in out)
    return out


@pytest.mark.parametrize("how", ["eager", "jit", "dp4_batch_sharded"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_mask_eagerly_under_jit_and_on_a_batch_sharded_mesh(dtype, how):
    """The iota is global: a batch shard reads its slice of the one mask,
    in the output and in the gradient."""
    x = _data((8, 16, 24), dtype)
    key = jax.random.PRNGKey(3)

    def value_and_grad(x):
        out, vjp = jax.vjp(lambda d: ops_nn._dropout(
            d, key, p=0.1, _train=True), x)
        return out, vjp(x)[0]
    out, dx = _traced(how, value_and_grad, jnp.asarray(x))
    want, mask = _twin(x, key, 0.1)
    assert 0 < mask.sum() < mask.size
    np.testing.assert_array_equal(np.asarray(out), want)
    np.testing.assert_array_equal(np.asarray(dx), want)


class TwoSites(HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.a, self.b = nn.Dropout(0.5), nn.Dropout(0.5)

    def hybrid_forward(self, F, x):
        return self.a(x), self.b(x)


@pytest.mark.parametrize("hybridize", [False, True])
def test_two_sites_and_two_steps_draw_different_masks(hybridize):
    """Each site gets a key of its own from the frontend's split, a new one
    every step: four masks that agree where independent ones would."""
    mx.random.seed(5)
    net = TwoSites()
    if hybridize:
        net.hybridize()
    x = mx.nd.ones((64, 256))
    masks = []
    for _step in range(2):
        with mx.autograd.record():
            masks += [np.asarray(y.asnumpy() != 0) for y in net(x)]
    sigma = math.sqrt(0.25 / x.size)
    for i, m in enumerate(masks):
        assert abs(m.mean() - 0.5) < 4 * sigma
        for other in masks[i + 1:]:
            assert abs((m == other).mean() - 0.5) < 4 * sigma


_KEYS = [(0, 0), (1, 0), (0xDEADBEEF, 0x12345678)]


@pytest.mark.parametrize("keep", [0.9, 0.5])
@pytest.mark.parametrize("key", _KEYS)
def test_keep_rate_overall_by_row_and_by_column(key, keep):
    m = dm.keep_mask(np.asarray(key, np.uint32), (4096, 768), keep, xp=np)
    sigma = lambda n: math.sqrt(keep * (1 - keep) / n)
    assert abs(m.mean() - keep) < 4 * sigma(m.size)
    for row in (0, 1, 1000, 4095):
        assert abs(m[row].mean() - keep) < 4 * sigma(768)
    for column in (0, 1, 500, 767):
        assert abs(m[:, column].mean() - keep) < 4 * sigma(4096)
    # and no row or column far out: 4,864 of them, so 5 sigma
    assert np.abs(m.mean(axis=1) - keep).max() < 5 * sigma(768)
    assert np.abs(m.mean(axis=0) - keep).max() < 5 * sigma(4096)


@pytest.mark.parametrize("step", [(1, 1), (1, 7), (64, 1), (2048, 384)])
@pytest.mark.parametrize("key", _KEYS)
def test_two_by_two_minors_are_jointly_independent(key, step):
    """A sum of a row hash and a column hash would make a mask's 2 x 2
    minors dependent (three corners would settle the fourth): all four
    kept happens at keep^4, over minors that share no element."""
    keep = 0.9
    m = dm.keep_mask(np.asarray(key, np.uint32), (4096, 768), keep, xp=np)
    di, dj = step
    i = np.arange(4096 - di)[(np.arange(4096 - di) // di) % 2 == 0]
    j = np.arange(768 - dj)[(np.arange(768 - dj) // dj) % 2 == 0]
    corners = [m[np.ix_(i + a, j + b)] for a in (0, di) for b in (0, dj)]
    joint = np.logical_and.reduce(corners)
    q = keep ** 4
    assert abs(joint.mean() - q) < 4 * math.sqrt(q * (1 - q) / joint.size)
    # the parity of the four, which a sum of two hashes would fix
    odd = np.logical_xor.reduce(corners)
    r = (1 - (2 * keep - 1) ** 4) / 2
    assert abs(odd.mean() - r) < 4 * math.sqrt(r * (1 - r) / odd.size)


def test_a_shape_past_32_bits_of_elements_folds_its_leading_index():
    shape = (3, 2 ** 31, 4)                      # 3 * 2^33 elements
    out = jax.eval_shape(
        lambda d, k: ops_nn._dropout(d, k, p=0.5, _train=True),
        jax.ShapeDtypeStruct(shape, jnp.bfloat16), jax.random.PRNGKey(0))
    assert out.shape == shape and out.dtype == jnp.bfloat16
    # every run of dimensions is indexed below 2^32
    assert [list(r) for r in dm._runs(shape)] == [[2], [1], [0]]
    assert [list(r) for r in dm._runs((5, 2 ** 20, 2 ** 11, 3))] == \
        [[2, 3], [0, 1]]
    assert [list(r) for r in dm._runs((40, 512, 768))] == [[0, 1, 2]]
    # elements whose flat index is equal modulo 2^32 draw different words
    shape = (8, 2 ** 20, 2 ** 12)
    assert [list(r) for r in dm._runs(shape)] == [[2], [0, 1]]
    key = np.asarray((11, 12), np.uint32)
    at = lambda *index: dm._mask_bits(
        key, shape, [np.full((1, 1, 1), i, np.uint32) for i in index],
        np).item()
    assert len({at(lead, 5, 77) for lead in range(8)}) == 8
    with pytest.raises(mx.MXNetError, match="32 bits"):
        dm._runs((2, 2 ** 32))


def test_a_folded_mask_is_one_mask_in_numpy_and_jnp(monkeypatch):
    """The fold on a shape small enough to draw: with words of 8 bits,
    (6, 16, 16, 16) folds its leading dimensions in two runs."""
    monkeypatch.setattr(dm, "_WORD", 1 << 8)
    shape, key = (6, 16, 16, 16), np.asarray((3, 4), np.uint32)
    assert [list(r) for r in dm._runs(shape)] == [[3], [2], [0, 1]]
    want = dm.keep_mask(key, shape, 0.5, xp=np)
    got = jax.jit(lambda k: dm.keep_mask(k, shape, 0.5))(jnp.asarray(key))
    np.testing.assert_array_equal(np.asarray(got), want)
    flat = want.reshape(6 * 16 * 16, 16)
    assert len({row.tobytes() for row in flat}) > 0.95 * len(flat)
    sigma = math.sqrt(0.25 / want.size)
    assert abs(want.mean() - 0.5) < 4 * sigma
    assert abs((want[0] == want[1]).mean() - 0.5) < 4 * sigma * math.sqrt(6)


@pytest.mark.parametrize("case", ["p=0", "inference", "always"])
def test_no_dropout_returns_the_data_itself(case):
    x, key = jnp.ones((4, 4)), jax.random.PRNGKey(0)
    before = dm.site_counts()
    if case == "p=0":
        assert ops_nn._dropout(x, key, p=0.0, _train=True) is x
    elif case == "inference":
        assert ops_nn._dropout(x, key, p=0.5, _train=False) is x
    else:
        out = ops_nn._dropout(x, key, p=0.5, mode="always", _train=False)
        assert 0 < int((np.asarray(out) == 0).sum()) < 16
    assert dm.site_counts()["hash"]["sites"] \
        - before["hash"]["sites"] == (case == "always")


class BertShaped(HybridBlock):
    """Two encoder layers with BERT's three kinds of site."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.embed_dropout = nn.Dropout(0.1)
            self.layer0 = BERTEncoderCell(128, 256, 2, dropout=0.1,
                                          prefix="layer0_")
            self.layer1 = BERTEncoderCell(128, 256, 2, dropout=0.1,
                                          prefix="layer1_")
        self.head = nn.Dense(4, prefix="classifier_")

    def hybrid_forward(self, F, x, mask):
        x = self.embed_dropout(x)
        return self.head(self.layer1(self.layer0(x, mask), mask))


def test_a_step_counts_its_sites_and_holds_no_threefry_draw():
    """1 + 2 a layer sites in a traced step, exported by generator, and no
    instruction of the compiled step samples: what is left of threefry is
    the scalar split of keys."""
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
    telemetry.enable()
    try:
        exported = lambda: telemetry.get_registry().get(
            "mx_dropout_sites_total").labels("hash").value
        before, exported_before = dm.site_counts()["hash"], exported()
        loss = trainer.step(rng.rand(2, 128, 128).astype("float32"),
                            np.ones((2, 128), "float32"),
                            rng.randint(0, 4, 2).astype(np.int32))
        after = dm.site_counts()["hash"]
        assert exported() - exported_before == 5
    finally:
        telemetry.disable()
    assert np.isfinite(float(loss.asnumpy().sum()))
    assert after["sites"] - before["sites"] == 5
    assert after["elements"] - before["elements"] == 5 * 2 * 128 * 128
    program = spmd.step_programs()[-1]
    names = set(program["ops"].values())
    assert any("/Dropout/" in n for n in names)
    assert not [n for n in names if "_bernoulli" in n or "_uniform" in n
                or "threefry2x32" in n]
