"""`parallel.moe.experts`: the row-wise stages go over a chunk in blocks
of `row_block(chunk)` rows under a count read from `group_sizes`, a token
sums its own rows (`_token_sums`: no scatter), and the two loops are
traced once a signature.  CPU, the `ragged_dot` route."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import parallel
from mxnet_tpu.parallel import moe

T, K, N, HELD = 1300, 8, 12, 4


@pytest.fixture(autouse=True)
def _ragged_dot(monkeypatch):
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")


def _hand_plan(used, rows, rng):
    """A plan of `rows` rows whose first `used` hold an assignment, as
    `route` lays them out: expert by expert, ascending tokens inside an
    expert (a token once an expert), then token T and weight 0."""
    sizes = np.full(HELD, used // HELD)
    sizes[:used % HELD] += 1
    token = np.concatenate(
        [np.sort(rng.choice(T, n, replace=False)) for n in sizes]
        + [np.full(rows - used, T)])
    weight = np.where(token < T, rng.rand(rows) + 0.5, 0)
    return moe.RoutePlan(jnp.asarray(token, jnp.int32),
                         jnp.asarray(weight, jnp.float32),
                         jnp.asarray(sizes, jnp.int32),
                         jnp.zeros((), jnp.int32))


def _act(hidden, form):
    if form == "relu2":
        return jnp.square(jnp.maximum(hidden, 0))
    gate, up = jnp.split(hidden, 2, axis=-1)
    return gate * jax.nn.sigmoid(gate) * up


def _per_row(u, plan, weight, w1, w2, form):
    """The plain form: every row by its own expert's matrices."""
    rows = plan.token.shape[0]
    expert = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(plan.group_sizes), jnp.arange(rows), side="right"),
        HELD - 1)
    x = jnp.take(u, plan.token, axis=0, mode="fill", fill_value=0)
    out = jnp.einsum("rn,rnk->rk", _act(jnp.einsum(
        "rk,rkn->rn", x, w1[expert]), form), w2[expert])
    out = jnp.where((plan.token < T)[:, None], out, 0) * weight[:, None]
    return jnp.zeros_like(u).at[plan.token].add(out, mode="drop")


def _weights(rng, form):
    wide = (2 if form == "silu_gated" else 1) * N
    return (jnp.asarray(rng.randn(T, K), jnp.float32),
            jnp.asarray(rng.randn(HELD, K, wide) * 0.3, jnp.float32),
            jnp.asarray(rng.randn(HELD, N, K) * 0.3, jnp.float32))


BLOCK = 1024     # of a chunk of ROW_CHUNK = 4,096 rows: a quarter
# rows of the plan, assignments made, (trips, blocks) the loops must take
_USED = {"none": (4096, 0, (0, 0)),
         "one_row": (4096, 1, (1, 1)),
         "block_less_one": (4096, BLOCK - 1, (1, 1)),
         "block": (4096, BLOCK, (1, 1)),
         "block_and_one": (4096, BLOCK + 1, (1, 2)),
         "full_chunk": (4096, 4096, (1, 4)),
         "two_trips": (8192, 5000, (2, 5))}


@pytest.mark.parametrize("form", moe.FORMS)
@pytest.mark.parametrize("case", list(_USED))
def test_experts_in_blocks_match_the_per_row_form(case, form):
    """Result and the gradients with respect to u, the combine weights,
    w1 and w2 are the plain per-row form's, wherever the assignments end
    against a block's edge."""
    rows, used, (trips, blocks) = _USED[case]
    rng = np.random.RandomState(rows + used)
    plan = _hand_plan(used, rows, rng)
    assert moe.row_chunk() == 4096 and moe.row_block(4096) == BLOCK
    assert int(moe.plan_chunks(plan.group_sizes)) == trips
    assert int(moe.plan_blocks(plan.group_sizes)) == blocks
    u, w1, w2 = _weights(rng, form)
    ct = jnp.asarray(rng.randn(T, K), jnp.float32)

    def loss(fn):
        def f(u, weight, w1, w2):
            out = fn(u, weight, w1, w2)
            return (out * ct).sum(), out
        return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3), has_aux=True))

    grads, out = loss(lambda u, weight, w1, w2: moe.experts(
        u, plan._replace(weight=weight), w1, w2, form))(
            u, plan.weight, w1, w2)
    want, want_out = loss(lambda u, weight, w1, w2: _per_row(
        u, plan, weight, w1, w2, form))(u, plan.weight, w1, w2)
    for got, ref in zip((out, *grads), (want_out, *want)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        assert np.isfinite(np.asarray(got)).all()
        # float32 sums over ~1,000 rows in another order
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
    if not used:
        assert not any(np.asarray(g).any() for g in (out, *grads))


# `_token_sums` alone: a chunk of 64 rows in blocks of 16, 20 tokens, at
# most 4 rows a token.  A case is the run of each token that has rows, in
# ascending token order: (token, rows), ...; the rows in token order are
# then 0, 1, 2, ... so a run's place against a block's edge is its sum
_T, _CHUNK, _BLOCK, _MOST, _K = 20, 64, 16, 4, 8


def _runs(lengths, first=0, step=1):
    return [(first + step * i, n) for i, n in enumerate(lengths)]


_SUMS = {
    "none": [],
    "one_row": [(7, 1)],
    "block_less_one": _runs([4, 4, 4, 3]),
    "block": _runs([4, 4, 4, 4]),
    "block_and_one": _runs([4, 4, 4, 4, 1]),
    # tokens with no row, with one and with the most a token can have
    "zero_one_and_most_rows": [(0, 1), (2, 4), (3, 1), (9, 4), (19, 4)],
    # rows 12-15 are one token's: its run ends on the block's last row
    "a_run_ends_on_a_blocks_last_row": _runs([4, 4, 4, 4, 2, 3]),
    # rows 14-17 and 30-33: runs that cross a block's edge
    "a_run_crosses_a_blocks_edge": _runs([4, 4, 4, 2, 4, 4, 4, 4, 4], 1, 2),
    "full_chunk": _runs([4] * 12 + [3] * 4 + [1] * 4),
}


@pytest.mark.parametrize("first", [True, False],
                         ids=["first_trip", "later_trip"])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "unweighted"])
@pytest.mark.parametrize("case", list(_SUMS))
def test_token_sums_are_the_plain_segment_sum(case, weighted, first):
    """Every token's sum over its own rows against `np.add.at` in
    float32, whatever the rows' order in the chunk; the rows that hold no
    assignment are NaN, as a kernel may leave them.  A later trip adds to
    the sum so far; the first one does not read it (NaN here)."""
    runs = _SUMS[case]
    used = sum(n for _, n in runs)
    assert all(0 <= tok < _T and 0 < n <= _MOST for tok, n in runs)
    if case == "full_chunk":
        assert used == _CHUNK
    if case == "a_run_ends_on_a_blocks_last_row":
        assert sum(n for _, n in runs[:4]) == _BLOCK
    if case == "a_run_crosses_a_blocks_edge":
        assert sum(n for _, n in runs[:4]) == _BLOCK - 2 and runs[4][1] == 4
        assert sum(n for _, n in runs[:8]) == 2 * _BLOCK - 2
    rng = np.random.RandomState(used)
    tok = np.full(_CHUNK, _T, np.int32)
    tok[:used] = np.repeat([tok for tok, _ in runs], [n for _, n in runs])
    tok = tok[rng.permutation(_CHUNK)]
    values = rng.randn(_CHUNK, _K).astype(np.float32)
    values[tok == _T] = np.nan
    wt = np.where(tok < _T, rng.rand(_CHUNK) + 0.5, 0).astype(np.float32)

    so_far = np.full((_T, _K), np.nan, np.float32) if first \
        else rng.randn(_T, _K).astype(np.float32)

    before = moe.route_counts()["token_sums"]
    sums = jax.jit(lambda so_far, first, values, tok, wt, count:
                   moe._token_sums(
                       "sums", so_far, first, values, tok,
                       wt if weighted else None, count, _MOST, _BLOCK))
    args = (jnp.asarray(so_far), jnp.bool_(first),
            jnp.asarray(values, jnp.bfloat16), jnp.asarray(tok),
            jnp.asarray(wt), jnp.int32(-(-used // _BLOCK)))
    got = sums(*args)
    assert moe.route_counts()["token_sums"] == before + 1
    assert got.shape == (_T, _K) and got.dtype == jnp.float32
    bf16 = np.asarray(jnp.asarray(values, jnp.bfloat16), np.float32)
    want = np.zeros((_T, _K), np.float32)
    np.add.at(want, tok[tok < _T], bf16[tok < _T] * (
        wt[tok < _T, None] if weighted else 1))
    absent = np.setdiff1d(np.arange(_T), tok)
    assert not want[absent].any()
    if not first:
        want = so_far + want
    # at most four float32 terms a token, added in another order
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got)[absent], want[absent])
    text = sums.lower(*args).as_text()
    assert "scatter" not in text and text.count("stablehlo.sort") == 1


@pytest.mark.parametrize("t, chunk", [(1, 8), (128, 256), (129, 512),
                                      (200, 64), (16384, 4096)])
def test_rows_in_token_order_and_each_tokens_count(t, chunk):
    """One stable sort by token; the count of a token's rows from the
    one-hot digits' product, exact whether or not T fills its last row
    of 128, and the run's start from its cumulative sum; token T (no
    assignment) is counted nowhere and sorts last."""
    rng = np.random.RandomState(t)
    tok = rng.randint(0, t + 1, size=chunk).astype(np.int32)
    order = jax.jit(lambda tok, wt: moe._in_token_order(tok, wt, t))
    wt = rng.rand(chunk).astype(np.float32)
    token, row, weight, rows, start = order(jnp.asarray(tok), jnp.asarray(wt))
    want = np.bincount(tok[tok < t], minlength=t)
    by_token = np.argsort(tok, kind="stable")
    np.testing.assert_array_equal(rows, want)
    np.testing.assert_array_equal(start, np.cumsum(want) - want)
    np.testing.assert_array_equal(row, by_token)
    np.testing.assert_array_equal(token, tok[by_token])
    np.testing.assert_array_equal(weight, wt[by_token])
    text = order.lower(jnp.asarray(tok), jnp.asarray(wt)).as_text()
    assert "scatter" not in text and text.count("stablehlo.sort") == 1


@pytest.mark.parametrize("form", moe.FORMS)
def test_a_token_whose_rows_straddle_two_trips_is_summed_over_both(form):
    """5,000 assignments in chunks of 4,096 rows: the tokens with a row in
    each of the two trips get both parts, in float32, and their gradient
    reaches u from both."""
    rng = np.random.RandomState(44)
    plan = _hand_plan(5000, 8192, rng)
    assert int(moe.plan_chunks(plan.group_sizes)) == 2
    token = np.asarray(plan.token)
    both = np.intersect1d(token[:4096], token[4096:5000])
    assert both.size > 100
    u, w1, w2 = _weights(rng, form)

    def total(fn):
        return jax.jit(jax.value_and_grad(
            lambda u: fn(u)[both].sum(), has_aux=False))

    got = total(lambda u: moe.experts(u, plan, w1, w2, form))(u)
    want = total(lambda u: _per_row(u, plan, plan.weight, w1, w2, form))(u)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    assert np.asarray(got[1])[both].any(axis=1).all()


@pytest.mark.parametrize("form", moe.FORMS)
def test_moe_apply_in_blocks_over_two_devices(form):
    """Under `shard_map` over 'ep' each device counts its own blocks (the
    bias loads the first device's experts with five blocks of rows, the
    second's with one); result and gradients are the one-piece layer's and the
    dense form's."""
    rng = np.random.RandomState(11)
    u, w1, w2 = _weights(rng, form)
    x = jnp.asarray(rng.randn(T, K), jnp.float32)
    wr = jnp.asarray(rng.randn(HELD, K) * 0.5, jnp.float32)
    bias = jnp.asarray([0.5, 0.5, 0.0, 0.0], jnp.float32)
    rows = moe.plan_rows(T, 2, 2)
    assert rows == 3072 and moe.row_block(rows) == 512
    blocks = [int(moe.plan_blocks(moe.route(
        x, wr, bias, top_k=2, first_expert=first, n_local=2).group_sizes,
        rows=rows)) for first in (0, 2)]
    assert blocks == [5, 1], blocks
    ct = jnp.asarray(rng.randn(T, K), jnp.float32)

    def dense(x, u, wr, w1, w2):
        score = jax.nn.sigmoid(x @ wr.T)
        chosen = jax.lax.top_k(score + bias, 2)[1]
        picked = jnp.take_along_axis(score, chosen, axis=1)
        weight = jnp.zeros_like(score).at[
            jnp.arange(T)[:, None], chosen].set(
                2.5 * picked / picked.sum(-1, keepdims=True))
        out = jnp.einsum("ten,enk->tek", _act(jnp.einsum(
            "tk,ekn->ten", u, w1), form), w2)
        return (out * weight[:, :, None]).sum(1)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: (fn(*a) * ct).sum(), argnums=(0, 1, 2, 3, 4)))

    def layer(x, u, wr, w1, w2):
        y, dropped = moe.moe_apply(x, u, wr, bias, w1, w2, top_k=2,
                                   scale=2.5, form=form)
        return y + 0 * dropped

    want = grads(dense)(x, u, wr, w1, w2)
    whole = grads(layer)(x, u, wr, w1, w2)
    with parallel.make_mesh(ep=2, devices=jax.devices()[:2]):
        split = grads(layer)(x, u, wr, w1, w2)
    for got, one, ref in zip(split, whole, want):
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(got, one, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sizes,expected,rows,blocks", [
    ([0, 0, 0], 0, None, 0),
    ([1, 0, 0], 0, None, 1),
    ([1024, 0, 0], 0, None, 1),
    ([1024, 1, 0], 0, None, 2),
    ([4096, 4096, 1], 0, None, 9),          # two whole chunks and a row
    ([4096, 4096, 1], 16384, None, 5),      # one chunk of 32,768: the same
    ([9000, 9000, 0], 16384, None, 9),      # 18,000 rows of a 32,768 chunk
    ([30000, 9000, 0], 16384, None, 16 + 4),
    ([300, 212, 0], 16384, 1536, 1),        # the plan is the chunk: 1,536
    ([300, 212, 1], 16384, 512 * 11, 2),    # 11 tiles: blocks of one tile
])
def test_plan_blocks_against_a_hand_count(sizes, expected, rows, blocks):
    got = moe.plan_blocks(jnp.asarray(sizes, jnp.int32), expected, rows)
    assert int(got) == blocks
    traced = jax.jit(lambda s: moe.plan_blocks(s, expected, rows))(
        jnp.asarray(sizes, jnp.int32))
    assert int(traced) == blocks


def test_row_block_divides_the_chunk():
    assert moe.ROW_BLOCK % moe.ROW_TILE == 0
    # `ROW_TILE` is what rows, chunks and blocks are padded to, no longer
    # the kernels' row tile: every chunk is whole tiles of any row tile
    # `choose_tile` may take below twice the unit
    assert all(moe.ROW_TILE % tm == 0 for tm in moe.ROW_TILES
               if tm <= moe.ROW_TILE)
    assert moe.row_block(32768) == moe.row_block(8192) == moe.ROW_BLOCK
    assert moe.row_block(4096) == moe.row_block(5120) == 1024  # a quarter
    assert moe.row_block(4608) == moe.row_block(512 * 11) == 512
    assert moe.row_block(512) == moe.row_block(1536) == 512
    for tiles in range(1, 70):
        chunk = tiles * moe.ROW_TILE
        block = moe.row_block(chunk)
        assert chunk % block == 0 == block % moe.ROW_TILE
        assert block <= max(min(moe.ROW_BLOCK, chunk // 4), moe.ROW_TILE)
        for kind in moe.KINDS:
            assert chunk % moe.choose_tile(kind, chunk, 256, 256, 4)[0] == 0


def _stack(layers, form, expected_rows=0):
    """Value and gradients of `layers` expert layers with weights of one
    shape, each under a `jax.checkpoint` closure of its own, traced (not
    run); -> what `route_counts()` rose by."""
    rng = np.random.RandomState(2)
    x, w1, w2 = _weights(rng, form)
    wr = jnp.asarray(rng.randn(HELD, K), jnp.float32)

    def layer(i):
        def f(x, w1, w2):
            plan = moe.route(x, wr, jnp.zeros((HELD,)), top_k=HELD,
                             scale=1.0 + i)
            return x + moe.experts(x, plan, w1, w2, form, expected_rows)
        return jax.checkpoint(f)

    def model(x, weights):
        for i, w in enumerate(weights):
            x = layer(i)(x, *w)
        return x.sum()

    before = moe.route_counts()
    jax.jit(jax.grad(model, argnums=1)).trace(x, [(w1, w2)] * layers)
    return {k: v - before[k] for k, v in moe.route_counts().items()}


def test_the_stage_is_traced_once_a_signature_not_once_a_layer():
    """Three layers of one shape, each its own recomputed segment,
    differentiated once, trace the expert stage as often as ONE layer
    does; the calls are still counted a layer.  Another `form` or another
    chunk is another signature."""
    jax.clear_caches()
    try:
        one = _stack(1, "relu2")
        assert one["ragged_dot"] == 2 and one["sorted_layout"] == 1
        assert 2 <= one["expert_stage_traces"] <= 3     # forward, backward
        # the sums are traced where a loop is: once a trace of either
        assert one["token_sums"] == one["expert_stage_traces"]
        jax.clear_caches()
        three = _stack(3, "relu2")
        assert three["ragged_dot"] == 6 and three["sorted_layout"] == 3
        assert three["expert_stage_traces"] == one["expert_stage_traces"]
        assert three["token_sums"] == three["expert_stage_traces"]
        again = _stack(3, "relu2")              # every call binds a trace
        assert again["ragged_dot"] == 6 and again["expert_stage_traces"] == 0
        assert again["token_sums"] == 0
        gated = _stack(3, "silu_gated")
        assert gated["expert_stage_traces"] == one["expert_stage_traces"]
        # a plan of 5,632 rows: row_chunk(2048) is the 4,096 it already
        # was, row_chunk(2049) = 4,608 is not
        assert _stack(3, "relu2", 2048)["expert_stage_traces"] == 0
        assert (_stack(3, "relu2", 2049)["expert_stage_traces"]
                == one["expert_stage_traces"])
    finally:
        jax.clear_caches()
