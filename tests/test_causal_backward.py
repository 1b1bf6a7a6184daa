"""The causal cores' backward of the repo's own (`mx_causal_attention_bwd`
over the triangle, `mx_window_attention_bwd` over a window's band,
`ops/pallas_attention.py`): one kernel that forms each visited score block
once and gives dK, dV and dQ, dQ summed in float32.  All under the Pallas
interpreter on the CPU: what the kernels compute, which calls take them,
how they walk, and that the sum over the key blocks is rounded once."""
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas_attention as pa

SCALE = 0.1


def _operands(groups, s, d, d_v, dtype, seed=0):
    """One batch row, one key/value head: q (1, groups, S, d), k (1, 1, S,
    d), v and the output's cotangent at d_v."""
    rng = np.random.RandomState(seed + groups + s + d)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), dtype)
    return (draw(1, groups, s, d), draw(1, 1, s, d), draw(1, 1, s, d_v),
            draw(1, groups, s, d_v))


def _gradients(core, q, k, v, ct):
    return jax.grad(lambda q, k, v: (core(q, k, v).astype(jnp.float32)
                                     * ct.astype(jnp.float32)).sum(),
                    argnums=(0, 1, 2))(q, k, v)


def _xla_gradients(q, k, v, ct):
    """`_causal_xla`'s gradients, a query head at a time: one head's S x S
    scores in memory, dK and dV summed over the group here."""
    dq, dk, dv = [], 0.0, 0.0
    for g in range(q.shape[1]):
        head = slice(g, g + 1)
        a, b, c = _gradients(lambda q, k, v: pa._causal_xla(q, k, v, SCALE),
                             q[:, head], k, v, ct[:, head])
        dq.append(a)
        dk, dv = dk + b, dv + c
    return jnp.concatenate(dq, axis=1), dk, dv


def _kernels(q, k, v, window=None):
    return pa._attend_causal(q, k, v, SCALE, window, True)


@pytest.mark.parametrize("d, d_v", [(128, 128), (64, 64), (192, 128)])
@pytest.mark.parametrize("s", [1024, 2048, 4096])
@pytest.mark.parametrize("groups", [1, 6, 16])
def test_fused_backward_matches_the_xla_form(groups, s, d, d_v):
    """dQ, dK and dV of the one kernel against `_causal_xla`'s, float32
    operands: 1, 2 and 4 key blocks (the first visit of a dQ block
    writes, every later one reads, adds and writes; at a group of one
    the last query block comes back in consecutive steps), the three
    pairs of head sizes a cell runs, dK and dV summed over the group."""
    assert pa._fused_backward(None, s, d, d_v, groups)
    q, k, v, ct = _operands(groups, s, d, d_v, jnp.float32)
    got = _gradients(_kernels, q, k, v, ct)
    for g, w in zip(got, _xla_gradients(q, k, v, ct)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4 * groups)


@pytest.mark.parametrize("d, d_v", [(128, 128), (64, 128), (64, 64)])
@pytest.mark.parametrize("s, window", [(1024, 256), (1024, 512), (2048, 512),
                                       (2048, 1024)])
@pytest.mark.parametrize("groups", [1, 2, 8])
def test_band_backward_matches_the_xla_form(groups, s, window, d, d_v):
    """dQ, dK and dV of a windowed call against `jax.grad` of
    `_window_xla`, float32 operands: windows of 2, 4 and 8 chunks of
    keys (an S of 2,048 rows or fewer is one block of the band kernel:
    every chunk's seen queries and both edges' masks within it), the
    head sizes of the two cells that run a window and 64 throughout, dK
    and dV summed over the group."""
    assert pa._fused_backward(window, s, d, d_v, groups) == "band"
    q, k, v, ct = _operands(groups, s, d, d_v, jnp.float32, seed=window)
    got = _gradients(functools.partial(_kernels, window=window), q, k, v, ct)
    want = _gradients(lambda q, k, v: pa._window_xla(q, k, v, SCALE, window),
                      q, k, v, ct)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4 * groups)


@pytest.mark.parametrize("d, d_v", [(128, 128), (64, 128)])
@pytest.mark.parametrize("rows, compute, window", [
    (512, 128, 512), (256, 128, 512), (256, 256, 256), (256, 128, 100),
    (128, 128, 300), (512, 256, 1024)])
@pytest.mark.parametrize("groups", [1, 3])
def test_band_kernel_over_several_blocks_matches_the_xla_form(
        groups, rows, compute, window, d, d_v):
    """The kernel itself at blocks smaller than a cell's, S 1,024: 2, 4
    and 8 key blocks, a query block reaching 1, 2 and 3 key blocks back
    (the held dQ blocks come back after as many key blocks), windows
    that are no multiple of a chunk, against `jax.grad` of `_window_xla`
    with the forward's statistics computed here."""
    s = 1024
    q, k, v, ct = _operands(groups, s, d, d_v, jnp.float32, seed=rows)
    want = _gradients(lambda q, k, v: pa._window_xla(q, k, v, SCALE, window),
                      q, k, v, ct)
    scaled = q * SCALE
    score = jnp.einsum("bhqd,bhkd->bhqk", scaled, jnp.repeat(k, groups, 1))
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None]
    lse = jax.scipy.special.logsumexp(
        jnp.where((ahead >= 0) & (ahead < window), score, -1e30), axis=-1)
    di = (pa._window_xla(q, k, v, SCALE, window) * ct).sum(-1)
    dq, dk, dv = pa._window_bwd_pallas(
        scaled[:, None], k, v, ct[:, None], lse[:, None], di[:, None],
        SCALE, window, rows, compute, True)
    for g, w in zip((dq[:, 0], dk, dv), want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4 * groups)


def _upstream_fused(q, k, v):
    """Upstream's `use_fused_bwd_kernel`: dQ as S / 1,024 partials in the
    operands' type, summed by XLA.  The yardstick, never a route."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    b, h, s, d = q.shape
    rows, compute = pa._splash_blocks(s, None)
    kernel = sa.make_splash_mqa_single_device(
        sa.MultiHeadMask([sa.CausalMask((s, s))] * h),
        block_sizes=sa.BlockSizes(
            block_q=rows, block_kv=rows, block_kv_compute=compute,
            block_q_dkv=rows, block_kv_dkv=rows,
            block_kv_dkv_compute=compute, use_fused_bwd_kernel=True),
        interpret=True)
    q = (q.astype(jnp.float32) * SCALE).astype(q.dtype)
    return jax.vmap(kernel)(q, k[:, 0], v[:, 0])


def _split(monkeypatch, window=None):
    """`_attend_causal` on upstream's split backward, as before PRs 48
    (the triangle) and 50 (a window)."""
    def core(q, k, v):
        with monkeypatch.context() as patch:
            patch.setattr(pa, "_fused_backward", lambda *a: "split")
            return pa._causal_splash(q, k, v, SCALE, window, interpret=True)
    return core


@pytest.mark.parametrize("s, window", [(4096, None), (8192, None),
                                       (2048, 512), (2048, 1024)])
def test_dq_is_summed_in_float32_and_rounded_once(s, window, monkeypatch):
    """bfloat16 operands, 4 and 8 key blocks of the triangle and a band
    of two and of three, the LAST 1,024 queries' rows (under the
    triangle the ones every key block adds to): dQ's largest and
    root-mean-square error against a float32 oracle on the same operands
    are no larger than the split kernels' (a float32 sum over the key
    blocks in each; rounded once here, in the kernel and again after the
    scale there).  Upstream's fused form, which rounds every key
    block's part to bfloat16 before the sum, fails the same two lines
    over the triangle: the test would catch partials."""
    q, k, v, ct = _operands(2, s, 128, 128, jnp.bfloat16)
    as_float32 = [x.astype(jnp.float32) for x in (q, k, v, ct)]
    exact = _xla_gradients(*as_float32)[0] if window is None else _gradients(
        lambda q, k, v: pa._window_xla(q, k, v, SCALE, window),
        *as_float32)[0]

    def errors(core):
        dq = _gradients(core, q, k, v, ct)[0]
        assert dq.dtype == jnp.bfloat16
        off = np.asarray(dq.astype(jnp.float32) - exact)[:, :, -1024:]
        return np.abs(off).max(), np.sqrt((off ** 2).mean())

    held = lambda ours, split: ours[0] <= split[0] and ours[1] <= split[1]
    split = errors(_split(monkeypatch, window))
    ours = errors(functools.partial(_kernels, window=window))
    assert held(ours, split), (ours, split)
    if window is None:
        assert not held(errors(_upstream_fused), split)
        assert errors(_upstream_fused)[1] > 1.05 * split[1]


def _kernel_names(jaxpr, into=None):
    """The Pallas kernels a jaxpr runs, by name, sub-jaxprs included."""
    into = [] if into is None else into
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            into.append(eqn.params["name"])
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_names(sub, into)
    return into


# (S, window) -> the form of the backward and the kernels it is made of
_SPLIT = ["splash_mqa_dkv_no_residuals", "splash_mqa_dq_no_residuals"]
_FORMS = {
    "triangle": (1024, None, "fused", ["mx_causal_attention_bwd"]),
    "window": (1024, 256, "band", ["mx_window_attention_bwd"]),
    "s_the_block_does_not_divide": (384, None, "split", _SPLIT),
}


@pytest.mark.parametrize("case", list(_FORMS))
def test_backward_form_is_counted_where_the_backward_is_traced(case):
    """A traced `jax.grad` of a causal call counts `fused` once and runs
    the one kernel; of a windowed call `band` once and the band's one
    kernel; of an S that 1,024 does not divide, `split` once and
    upstream's two, as before.  The forward routes' counts are not
    touched, and a call that is not differentiated counts nothing."""
    s, window, form, kernels = _FORMS[case]
    q = jnp.zeros((1, 2, s, 128), jnp.bfloat16)
    k = v = jnp.zeros((1, 1, s, 128), jnp.bfloat16)
    core = lambda q, k, v: pa._attend_causal(q, k, v, SCALE, window, True)
    routes, before = pa.route_counts(), pa.backward_counts()
    jax.make_jaxpr(core)(q, k, v)
    assert pa.backward_counts() == before
    program = jax.make_jaxpr(jax.grad(
        lambda q, k, v: core(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    after = pa.backward_counts()
    assert after[form] == before[form] + 1
    assert sum(after.values()) == sum(before.values()) + 1
    assert pa.route_counts() == routes
    assert _kernel_names(program.jaxpr) == [
        "splash_mqa_fwd_residuals"] + kernels


@pytest.mark.parametrize("shape, form", [
    ((None, 8192, 128, 128, 6), "fused"), ((None, 8192, 192, 128, 1), "fused"),
    ((None, 8192, 64, 64, 4), "fused"), ((None, 8192, 128, 128, 16), "fused"),
    ((None, 1024, 128, 128, 1), "fused"), ((512, 8192, 128, 128, 8), "band"),
    ((512, 16384, 64, 128, 2), "band"), ((256, 1024, 128, 128, 2), "band"),
    ((1024, 2048, 128, 128, 2), "band"), ((256, 384, 128, 128, 1), "band"),
    ((512, 8192, 128, 128, 256), "split"),
    ((None, 384, 128, 128, 1), "split"), ((None, 1536, 128, 128, 1), "split")])
def test_which_calls_take_the_fused_backward(shape, form):
    """(window, S, d, d_v, query heads a key/value head) alone decide:
    every causal core a cell runs at S 8192 is `fused`; a window is
    `band` at both cells' shapes, smaller than the block, twice it and
    at an S of 128-row blocks, but not where a group's dQ blocks fit
    the kernel's VMEM at no block; no S that the block of 1,024 rows
    does not divide takes the triangle's kernel."""
    assert pa._fused_backward(*shape) == form


def test_the_walk_visits_the_triangle_key_block_outermost():
    """36 of the 64 block pairs at 8 blocks, each once a query head; a
    key block's steps are consecutive (its dK and dV are one sum in
    VMEM); `again` marks the one step of a group of one that follows a
    step on the same dQ block, and no step of a larger group."""
    walk = pa._triangle_walk(8, 1)
    assert walk.shape == (4, 36)
    pairs = list(zip(walk[0], walk[2]))
    assert len(set(pairs)) == 36 and all(qb >= kb for kb, qb in pairs)
    assert list(walk[0]) == sorted(walk[0])
    assert list(np.flatnonzero(walk[3])) == [35]
    assert (pairs[34], pairs[35]) == ((6, 7), (7, 7))
    assert list(np.flatnonzero(pa._triangle_walk(2, 1)[3])) == [2]
    grouped = pa._triangle_walk(8, 6)
    assert grouped.shape == (4, 216) and not grouped[3].any()
    # every query block's first visit is key block 0: nothing to read
    first = {}
    for kb, g, qb, _ in grouped.T:
        first.setdefault((g, qb), kb)
    assert set(first.values()) == {0}


def _area(window, n):
    """The pairs of 0 <= i - j < window among n queries and n keys."""
    return sum(min(i + 1, window) for i in range(n))


@pytest.mark.parametrize("rows, window", [(512, 256), (512, 512),
                                          (512, 1024), (1024, 512),
                                          (128, 300)])
def test_the_band_walk_visits_every_block_pair_of_the_band_once(rows, window):
    """8 blocks: every (key block, query block) pair with a pair inside
    the band once and none outside it; a key block's steps are
    consecutive (its dK and dV are one sum in VMEM) and end on the
    diagonal, the LAST visit of that query block (dQ's block index is
    the key block's over those steps, so it is written whole); and of
    the `reach + 1` dQ blocks a head holds by the query block modulo
    that, none is begun before the one in its place is done."""
    blocks, reach = 8, pa._band_reach(rows, window)
    steps = [tuple(int(x) for x in col)
             for col in pa._band_walk(blocks, reach).T]
    inside = {(kb, qb) for kb in range(blocks) for qb in range(blocks)
              if any(0 <= qb * rows + r - kb * rows - c < window
                     for r in (0, rows - 1) for c in (0, rows - 1))}
    assert len(steps) == len(set(steps)) and set(steps) == inside
    runs = [list(run) for _, run in itertools.groupby(
        steps, key=lambda step: step[0])]
    assert [run[0][0] for run in runs] == list(range(blocks))
    assert all(run[-1] == (run[-1][0],) * 2 for run in runs)
    alive = {}                                   # place -> query block
    for kb, qb in steps:
        assert alive.setdefault(qb % (reach + 1), qb) == qb
        if qb == kb:
            del alive[qb % (reach + 1)]
    assert not alive


@pytest.mark.parametrize("rows, compute, window", [
    (512, 256, 512), (512, 512, 512), (512, 256, 256), (512, 256, 1024),
    (2048, 128, 512), (1024, 512, 512), (512, 128, 384), (256, 128, 100)])
def test_the_band_tiles_cover_the_band_and_say_which_edge_cuts(
        rows, compute, window):
    """Over 8 blocks, the pairs inside the band that the tiles of every
    block pair's offset hold, with the edges masked where a tile says
    it is cut and ONLY there, are the band's area exactly: no pair is
    lost to queries left out or counted under a mask that was not asked
    for; a tile says it is cut exactly where an edge crosses it, and
    starts and ends with a chunk of queries that sees the chunk of
    keys.  At rows 2,048, window 512 and chunks of 128 keys the tiles
    hold 1.25 times the band (640 queries a chunk but at the ends)."""
    blocks, reach = 8, pa._band_reach(rows, window)
    held = products = 0
    for offset in range(reach + 1):
        for c0, r0, n, below, above in pa._band_tiles(
                offset, rows, compute, window):
            assert c0 % compute == 0 and r0 % compute == 0 and n % compute == 0
            ahead = (offset * rows + r0 + np.arange(n)[None]
                     - c0 - np.arange(compute)[:, None])
            inside = (ahead >= 0) & (ahead < window)
            assert inside[:, :compute].any() and inside[:, -compute:].any()
            assert below == (ahead < 0).any()
            assert above == (ahead >= window).any()
            seen = np.ones_like(inside)
            if below:
                seen &= ahead >= 0
            if above:
                seen &= ahead < window
            assert (inside == seen).all()
            held += seen.sum() * (blocks - offset)
            products += seen.size * (blocks - offset)
    area = _area(window, blocks * rows)
    assert held == area
    if (rows, compute, window) == (2048, 128, 512):
        assert 1.24 < products / area < 1.26


def _simulated():
    """(jax's simulator of the TPU's copies and semaphores with its race
    detector on, what reads the detector's verdict on the last kernel
    run); private to jax, so skip where it has moved."""
    try:
        from jax._src.pallas.mosaic.interpret import interpret_pallas_call
        from jax.experimental.pallas import tpu as pltpu
        return (pltpu.InterpretParams(detect_races=True,
                                      dma_execution_mode="on_wait"),
                lambda: interpret_pallas_call.races.races_found)
    except (ImportError, AttributeError) as e:
        pytest.skip(f"no TPU interpret mode with race detection here: {e}")


@pytest.mark.parametrize("blocks, groups", [(2, 1), (3, 1), (2, 2)])
def test_a_dq_block_is_read_after_its_last_write_has_landed(
        blocks, groups, monkeypatch):
    """The kernel's own copies of dQ, simulated with their semaphores:
    no read of a block races the write before it, at a group of one over
    two and three key blocks (the last query block comes back in the
    very next step: the write is waited for before the read starts) and
    at a group of two (it comes back two steps later: the write was
    waited for a step before).  A walk that never marks `again` is
    caught, so the detector sees what this test is about."""
    simulated, raced = _simulated()
    s, d = blocks * 1024, 128
    rng = np.random.RandomState(blocks + groups)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    operands = (draw(1, 1, groups, s, d), draw(1, 1, s, d), draw(1, 1, s, d),
                draw(1, 1, groups, s, d), draw(1, 1, groups, s) + 8.0,
                draw(1, 1, groups, s))
    want = pa._causal_bwd_pallas(*operands, 1024, 512, True)
    got = jax.block_until_ready(
        pa._causal_bwd_pallas(*operands, 1024, 512, simulated))
    assert not raced()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    if groups == 1:
        walk = pa._triangle_walk
        monkeypatch.setattr(pa, "_triangle_walk", lambda *a: walk(
            *a) * np.array([[1], [1], [1], [0]], np.int32))
        jax.block_until_ready(
            pa._causal_bwd_pallas(*operands, 1024, 512, simulated))
        assert raced()
