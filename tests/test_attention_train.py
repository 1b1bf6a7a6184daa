"""The training route of `dot_product_attention` (PR 26): dropout on the
attention probabilities through two fused kernels (forward; dQ, dK and dV
in one backward), the mask an integer hash of (key, b*h, q, k) that the
kernels, the Pallas interpreter, XLA and numpy all regenerate bit for bit.
Everything here runs on the CPU: the kernels in interpret mode against
the XLA path under the IDENTICAL mask, the hash's statistics, the route's
choice by shape, and the scopes the backward kernel is booked under.  The
ahead-of-time compile for the v5e is in tests/test_chip_smoke.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import pallas_attention as pa

KEEP = 0.9


def _operands(batch, heads, seq, dim, valid, dtype, seed=0, first_row=5):
    """Packed (B, S, H*D) q, k, v and cotangent, a (B, S) prefix-valid
    mask from `valid`, the kernels' seed: two key words and the global
    index of the first batch row (not 0: the operands stand for one shard
    of a larger batch, and kernel and reference must index alike)."""
    rng = np.random.RandomState(seed)
    q, k, v, do = (jnp.asarray(rng.randn(batch, seq, heads * dim), dtype)
                   for _ in range(4))
    mask = jnp.asarray(np.arange(seq)[None, :] < np.asarray(valid)[:, None],
                       dtype)
    key = pa._seed(jnp.asarray(rng.randint(0, 2 ** 31, size=2), jnp.uint32),
                   first_row)
    return q, k, v, do, mask, key


def _kernels(q, k, v, do, mask, key, heads, blocks):
    scale = 1.0 / np.sqrt(q.shape[-1] // heads)
    o, lse = pa._attention_train_fwd_pallas(q, k, v, mask, key, heads, scale,
                                            KEEP, blocks)
    return (o, *pa._attention_train_bwd_pallas(q, k, v, mask, key, o, lse,
                                               do, heads, scale, KEEP,
                                               blocks))


def _reference(q, k, v, do, mask, key, heads):
    """The XLA path in f32 from the same operands, same mask."""
    f32 = lambda x: x.astype(jnp.float32)
    scale = 1.0 / np.sqrt(q.shape[-1] // heads)
    o, vjp = jax.vjp(
        lambda q_, k_, v_: pa._train_xla(q_, k_, v_, f32(mask), key, heads,
                                         scale, KEEP), f32(q), f32(k), f32(v))
    return (o, *vjp(f32(do)))


# (batch, heads, seq, dim, valid lengths,
#  (batch rows per step, query rows per block, rows unrolled in the loop))
CASES = [
    (4, 2, 128, 64, (128, 2, 3, 77), (1, 128, 1)),
    (4, 2, 128, 64, (128, 2, 3, 77), (4, 128, 2)),    # several rows a step
    (6, 4, 128, 64, (5, 128, 100, 2, 64, 3), (3, 128, 3)),
    (2, 2, 256, 64, (256, 131), (1, 128, 1)),         # two query blocks
    (2, 2, 256, 64, (3, 2), (2, 256, 2)),
    (2, 2, 512, 64, (3, 400), (1, 256, 1)),
    (2, 4, 512, 64, (512, 129), (2, 128, 1)),         # four query blocks
    (2, 1, 512, 128, (2, 511), None),                 # the code's own blocks
    (2, 3, 256, 128, (256, 2), (1, 128, 1)),          # one head a lane block
    (2, 1, 128, 256, (3, 128), None),
]


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("batch,heads,seq,dim,valid,blocks", CASES)
def test_kernels_match_the_reference_under_the_identical_mask(
        monkeypatch, batch, heads, seq, dim, valid, blocks, dtype, tol):
    """Outputs and dq/dk/dv, interpret mode: f32 at 1e-4, bf16 at 2e-2 of
    the tensor's largest value (the rounding of a sum over S keys scales
    with the tensor, not with the element).  Lengths 2 and 3 mask whole
    key blocks; padded QUERY rows still attend to the valid keys and must
    come out finite."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    ops = _operands(batch, heads, seq, dim, valid, dtype)
    got = _kernels(*ops, heads, blocks)
    ref = _reference(*ops, heads)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), name


@pytest.mark.parametrize("seq,small,large", [
    (256, (1, 128, 1), (2, 256, 2)), (512, (2, 128, 1), (1, 512, 1)),
    (128, (1, 128, 1), (4, 128, 4))])
def test_block_sizes_cannot_change_the_result(monkeypatch, seq, small, large):
    """The hash indexes by global (b*h, q, k): the same elements drop under
    any blocks.  dK and dV accumulate over query blocks in another order,
    so they agree to f32 rounding; o, dq and the mask agree exactly."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    ops = _operands(4, 2, seq, 64, (seq, 2, 3, seq // 2 + 1), "float32")
    a, b = _kernels(*ops, 2, small), _kernels(*ops, 2, large)
    for name, x, y in zip(("o", "dq", "dk", "dv"), a, b):
        x, y = np.asarray(x), np.asarray(y)
        atol = 1e-5 * max(1.0, np.abs(y).max()) if name in ("dk", "dv") else 0
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5 + atol,
                                   err_msg=name)


def _admitted():
    """(batch, heads, seq, dim) over the set the route admits, at the
    blocks the code chooses: every S, prime and odd batches, a batch the
    block does not divide evenly into its budget, the wider lane blocks."""
    for seq in range(128, pa._FUSED_MAX_SEQ + 1, 128):
        for batch in (1, 7) + ((40,) if seq <= 512 else ()):
            yield batch, 2, seq, 64
    for seq, dim in ((640, 128), (768, 128), (1024, 128), (384, 256),
                     (896, 256)):
        yield 3, 1, seq, dim


@pytest.mark.parametrize("batch,heads,seq,dim", list(_admitted()))
def test_every_admitted_shape_matches_the_reference_through_the_op(
        monkeypatch, batch, heads, seq, dim):
    """`_fused_train_shape` and `_train_blocks` together, as a model calls
    them: value and dq/dk/dv through `dot_product_attention` under the
    interpreter against the f32 XLA path under the identical mask.  S =
    640, 768 and 896 are the shapes a 512-row query block does not divide
    (REVIEW of PR 26: rows past 512 were never written)."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    bb, bq, unroll = pa._train_blocks(batch, seq, max(128, dim))
    assert seq % bq == 0 and batch % bb == 0 and bb % unroll == 0
    valid = ([2, 3, seq, seq // 2 + 1, seq - 1] * batch)[:batch]
    q, k, v, do, mask, _ = _operands(batch, heads, seq, dim, valid, "float32")
    key = jax.random.PRNGKey(seq + batch)
    before = pa.route_counts()
    o, vjp = jax.vjp(lambda q_, k_, v_: pa._dot_product_attention(
        q_, k_, v_, mask, key, num_heads=heads, dropout=1 - KEEP,
        _train=True), q, k, v)
    assert pa.route_counts()["fused_train"] == before["fused_train"] + 1
    ref = _reference(q, k, v, do, mask, pa._seed(pa._key_words(key)), heads)
    for name, a, b in zip(("o", "dq", "dk", "dv"), (o, *vjp(do)), ref):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= 1e-4 * max(1.0, np.abs(b).max()), name


def _kept_by_the_kernel(key, batch, heads, seq, blocks, first_row=0):
    """The mask the forward kernel applied, read off its output: q = k = 0
    makes every probability 1/S, and head h's v is the identity in ITS
    lanes, so o[b, q, h*S + k] > 0 exactly where (b*H + h, q, k) was kept
    (v's width is the head size: S here)."""
    zeros = jnp.zeros((batch, seq, heads * seq), jnp.float32)
    v = jnp.tile(jnp.eye(seq, dtype=jnp.float32), (batch, 1, heads))
    o, _ = pa._attention_train_fwd_pallas(
        zeros, zeros, v, jnp.ones((batch, seq)),
        pa._seed(jnp.asarray(key), first_row), heads, 1.0, KEEP, blocks)
    return (np.asarray(o).reshape(batch, seq, heads, seq).transpose(
        0, 2, 1, 3).reshape(batch * heads, seq, seq) > 0), np.asarray(o)


def test_dropped_elements_are_the_hashes(monkeypatch):
    """The kernel drops exactly the hash's elements, indexed b*H + h, and
    rescales what it keeps by 1/keep.  A shard whose first batch row is
    global row 3 drops rows 3.. of the whole batch's mask."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    batch, heads, seq = 3, 2, 128
    key = np.asarray([0xCAFEF00D, 77], np.uint32)
    whole = pa.dropout_keep_mask(key, 2 * batch * heads, seq, seq, KEEP,
                                 xp=np)
    np.testing.assert_array_equal(
        whole[batch * heads:], pa.dropout_keep_mask(
            key, batch * heads, seq, seq, KEEP, xp=np,
            first_head=batch * heads))
    for blocks in ((1, 128, 1), (3, 128, 3)):
        for first_row in (0, batch):
            kept, o = _kept_by_the_kernel(key, batch, heads, seq, blocks,
                                          first_row)
            np.testing.assert_array_equal(
                kept, whole[first_row * heads:(first_row + batch) * heads])
            np.testing.assert_allclose(o[o > 0], 1.0 / (seq * KEEP),
                                       rtol=1e-6)


# ---- the hash -------------------------------------------------------------

_BH, _SEQ = 48, 512                    # 12.6 M draws
_KEYS = ((0, 0), (1, 2), (0xDEADBEEF, 0x12345678), (12345, 2 ** 31))


@pytest.fixture(scope="module")
def masks():
    return {key: pa.dropout_keep_mask(np.asarray(key, np.uint32), _BH, _SEQ,
                                      _SEQ, KEEP, xp=np) for key in _KEYS}


def _corr(a, b):
    return np.corrcoef(a.ravel().astype(np.float64),
                       b.ravel().astype(np.float64))[0, 1]


@pytest.mark.parametrize("key", _KEYS)
def test_hash_keep_rate_and_neighbour_correlations(masks, key):
    m = masks[key]
    n = m.size
    assert n >= 1e7
    assert abs(m.mean() - KEEP) < 4 * np.sqrt(KEEP * (1 - KEEP) / n)
    bound = 4 / np.sqrt(n)
    assert abs(_corr(m[:, :, 1:], m[:, :, :-1])) < bound      # along k
    assert abs(_corr(m[:, 1:], m[:, :-1])) < bound            # along q
    assert abs(_corr(m[1:], m[:-1])) < bound                  # along b*h
    assert abs(_corr(m[:, 1:, 1:], m[:, :-1, :-1])) < bound   # the diagonal


@pytest.mark.parametrize("word", [0, 1])
def test_hash_two_keys_are_uncorrelated(masks, word):
    """Keys that differ by one in either word: the worst neighbours a
    split can hand out."""
    base = (12345, 2 ** 31)
    other = list(base)
    other[word] += 1
    m2 = pa.dropout_keep_mask(np.asarray(other, np.uint32), _BH, _SEQ, _SEQ,
                              KEEP, xp=np)
    assert abs(_corr(masks[base], m2)) < 4 / np.sqrt(m2.size)


def test_hash_is_equal_in_numpy_jnp_and_the_interpreter(monkeypatch):
    key = np.asarray([0xCAFEF00D, 77], np.uint32)
    bh, seq = 5, 256
    want = pa.dropout_keep_mask(key, bh, seq, seq, KEEP, xp=np)
    got = jax.jit(lambda kw: pa.dropout_keep_mask(kw, bh, seq, seq, KEEP))(
        jnp.asarray(key))
    np.testing.assert_array_equal(np.asarray(got), want)
    # rectangular, as the XLA path's causal and cross calls use it
    np.testing.assert_array_equal(
        np.asarray(pa.dropout_keep_mask(jnp.asarray(key), 3, 40, 72, KEEP)),
        pa.dropout_keep_mask(key, 3, 40, 72, KEEP, xp=np))
    # in the interpreter, two query blocks of a (1, 256)-headed call
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    kept, _ = _kept_by_the_kernel(key, bh, 1, seq, (1, 128, 1))
    np.testing.assert_array_equal(kept, want)


def test_a_typed_key_and_a_raw_key_give_the_same_words():
    raw = jax.random.PRNGKey(7)
    typed = jax.random.wrap_key_data(raw)
    np.testing.assert_array_equal(np.asarray(pa._key_words(raw)),
                                  np.asarray(pa._key_words(typed)))
    assert pa._key_words(raw).dtype == jnp.uint32


# ---- the route ------------------------------------------------------------

def _counted(before):
    """Routes chosen since `before`, zeros left out."""
    return {r: n - before[r] for r, n in pa.route_counts().items()
            if n != before[r]}


def _call(b, h, sq, sk, d, *, train=True, dropout=0.1, causal=False,
          key=True):
    q = jnp.ones((b, sq, h * d), jnp.float32)
    kv = jnp.ones((b, sk, h * d), jnp.float32)
    return jax.eval_shape(
        lambda q, kv: pa._dot_product_attention(
            q, kv, kv, jnp.ones((b, sk)),
            jax.random.PRNGKey(0) if key else None, num_heads=h,
            dropout=dropout, causal=causal, _train=train), q, kv)


@pytest.mark.parametrize("kwargs,route", [
    (dict(b=2, h=12, sq=128, sk=128, d=64), "fused_train"),     # BERT-base
    (dict(b=2, h=12, sq=512, sk=512, d=64), "fused_train"),
    (dict(b=1, h=2, sq=256, sk=256, d=128), "fused_train"),
    (dict(b=2, h=8, sq=128, sk=128, d=64, causal=True), "xla_dropout"),
    (dict(b=2, h=8, sq=128, sk=256, d=64), "xla_dropout"),      # cross
    (dict(b=2, h=8, sq=100, sk=100, d=64), "xla_dropout"),      # ragged
    (dict(b=2, h=2, sq=8, sk=8, d=8), "xla_dropout"),           # toy nets
    (dict(b=2, h=3, sq=128, sk=128, d=64), "xla_dropout"),      # half a block
    (dict(b=2, h=4, sq=128, sk=128, d=32), "xla_dropout"),
    (dict(b=1, h=2, sq=2048, sk=2048, d=64), "xla_dropout"),    # past VMEM
    (dict(b=1, h=2, sq=1152, sk=1152, d=64), "xla_dropout"),
    (dict(b=2, h=2, sq=640, sk=640, d=64), "fused_train"),
    (dict(b=2, h=2, sq=192, sk=192, d=64), "xla_dropout"),      # not 128s
    (dict(b=2, h=2, sq=128, sk=128, d=192), "xla_dropout"),
    (dict(b=2, h=12, sq=128, sk=128, d=64, train=False), "kernel_infer"),
    (dict(b=2, h=12, sq=128, sk=128, d=64, dropout=0.0), "kernel_infer"),
    (dict(b=2, h=12, sq=128, sk=128, d=64, key=False), "kernel_infer"),
])
def test_route_is_chosen_from_the_shape(kwargs, route):
    before = pa.route_counts()
    _call(**kwargs)
    assert _counted(before) == {route: 1}


def test_use_pallas_0_keeps_its_meaning(monkeypatch):
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    before = pa.route_counts()
    _call(b=2, h=12, sq=128, sk=128, d=64)
    _call(b=2, h=12, sq=128, sk=128, d=64, train=False)
    assert _counted(before) == {"xla_dropout": 1, "reference": 1}


def test_route_counts_reach_the_telemetry_counter():
    telemetry.enable()
    try:
        fam = lambda: telemetry.get_registry().get("mx_attention_route_total")
        before = fam().labels("fused_train").value if fam() else 0
        _call(b=1, h=2, sq=128, sk=128, d=64)
        assert fam().labels("fused_train").value == before + 1
    finally:
        telemetry.disable()


@pytest.mark.parametrize("mode", ["lowered_for_cpu", "interpret", "xla"])
def test_the_route_computes_one_function_on_every_platform(monkeypatch, mode):
    """Value and gradients through the op: the XLA reference a CPU program
    lowers to, the kernels under the interpreter and MXNET_USE_PALLAS=0
    agree, because all three take the mask from the same hash."""
    b, h, s, d = 2, 2, 128, 64
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(b, s, h * d), "float32")
               for _ in range(3))
    valid = jnp.asarray(np.arange(s)[None] < np.array([[s], [3]]), "float32")
    key = jax.random.PRNGKey(3)

    def loss(q, k, v):
        o = pa._dot_product_attention(q, k, v, valid, key, num_heads=h,
                                      dropout=0.1, _train=True)
        return (o ** 2).sum()

    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("MXNET_USE_PALLAS", "0" if mode == "xla" else "1")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET",
                       "1" if mode == "interpret" else "0")
    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(w).max()))


def _op_value_and_grads(mesh, heads, operands, key):
    """Value and gradients through the op, traced as `SPMDTrainer` traces
    the step: inside the mesh's scope, under a jit whose operands are
    sharded over the batch.  `mesh` None: one device, no scope."""
    import contextlib

    from jax.sharding import NamedSharding, PartitionSpec as P

    q, k, v, do, mask = operands

    def value_and_grads(q, k, v, do, mask):
        with mesh or contextlib.nullcontext():
            o, vjp = jax.vjp(lambda q_, k_, v_: pa._dot_product_attention(
                q_, k_, v_, mask, key, num_heads=heads, dropout=1 - KEEP,
                _train=True), q, k, v)
        # the pullback runs after the scope has closed, as jax.grad's does
        return (o, *vjp(do))

    if mesh is None:
        return jax.jit(value_and_grads)(*operands)
    rows = NamedSharding(mesh.mesh, P(tuple(mesh.axis_sizes)))
    return jax.jit(value_and_grads, in_shardings=rows, out_shardings=rows)(
        *(jax.device_put(x, rows) for x in operands))


@pytest.mark.parametrize("mode", ["lowered_for_cpu", "interpret"])
@pytest.mark.parametrize("axes", [dict(dp=8), dict(dp=2, fsdp=4)])
def test_a_batch_sharded_step_computes_the_one_device_function(
        monkeypatch, axes, mode):
    """GSPMD cannot partition a Mosaic call, so under a mesh that splits
    the batch the route runs one call a shard inside a shard_map; each
    shard's first GLOBAL row goes into the hash, so outputs and gradients
    equal the one-device call's, mask and all."""
    from mxnet_tpu import parallel

    monkeypatch.setenv("MXNET_PALLAS_INTERPRET",
                       "1" if mode == "interpret" else "0")
    batch, heads, seq = 16, 2, 128
    valid = [2, 3, seq, 77] * 4
    operands = _operands(batch, heads, seq, 64, valid, "float32")[:5]
    key = jax.random.PRNGKey(11)
    want = _op_value_and_grads(None, heads, operands, key)
    before = pa.route_counts()
    got = _op_value_and_grads(parallel.make_mesh(axes), heads, operands, key)
    assert _counted(before) == {"fused_train": 1}
    for name, a, w in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=1e-5,
                                   atol=1e-5, err_msg=name)


@pytest.mark.parametrize("axes,batch", [
    (dict(dp=4, tp=2), 16),      # the heads may be split: GSPMD's to do
    (dict(dp=2, sp=4), 16),
    (dict(dp=8), 12),            # the batch does not split evenly
])
def test_other_meshes_stay_on_the_route_gspmd_partitions(axes, batch):
    from mxnet_tpu import parallel

    before = pa.route_counts()
    with parallel.make_mesh(axes):
        _call(b=batch, h=2, sq=128, sk=128, d=64)
    assert _counted(before) == {"xla_dropout": 1}


def test_inference_is_untouched_by_the_key(monkeypatch):
    """A dropout-free call never reads the key and matches the reference."""
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(2, 128, 128), "float32")
               for _ in range(3))
    outs = [pa._dot_product_attention(q, k, v, None, key, num_heads=2,
                                      dropout=0.1, _train=False)
            for key in (jax.random.PRNGKey(0), jax.random.PRNGKey(1), None)]
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[2]))
