"""`parallel.moe.choose_tile`: every grouped product gets the tile its own
kind and dimensions ask for, and `parallel.moe._gmm` is the rule that
hands each of a product's three kernels (forward, gradient to lhs,
gradient to rhs) its own.  CPU: the rules by count, the kernels through
the Pallas interpreter against `lax.ragged_dot`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.parallel import moe

# cell -> (chunk m, K, N, w1's width over N, held experts, rows a group)
CELLS = {
    "lfm2_8b_a1b_s8192": (32768, 2048, 1792, 2, 8, 2048),
    "laguna_xs2_s8192": (32768, 2048, 512, 2, 32, 512),
    "joyai_llm_flash_s8192": (32768, 2048, 768, 2, 32, 512),
    "nemotron3_super_s8192": (4096, 1024, 2688, 1, 8, 512),
}


def _products(k, n, wide):
    """The six grouped products a chunk: (name, kind, k, n) AS EACH RUNS:
    u W1 and act W2, their gradients to lhs (k and n change places) and
    to rhs."""
    w = wide * n
    return (("p1", "gmm", k, w), ("p2", "gmm", n, k),
            ("p1_dlhs", "dlhs", w, k), ("p2_dlhs", "dlhs", k, n),
            ("p1_drhs", "tgmm", k, w), ("p2_drhs", "tgmm", n, k))


_CELL_PRODUCTS = [
    pytest.param(kind, m, k, n, held, 2, rows, id=f"{cell}-{name}")
    for cell, (m, K, N, wide, held, rows) in CELLS.items()
    for name, kind, k, n in _products(K, N, wide)]

_ODD_PRODUCTS = [
    pytest.param(kind, m, k, n, groups, itemsize, rows, id=f"{tag}-{kind}")
    for tag, (m, k, n, groups, itemsize, rows) in {
        "k_has_no_cut": (4096, 200, 1024, 4, 2, 0),
        "n_has_no_cut": (4096, 1024, 330, 4, 2, 0),
        "neither_has": (2048, 72, 40, 4, 2, 0),
        "one_row_tile": (512, 1024, 2688, 8, 2, 0),
        "one_group": (8192, 4096, 4096, 1, 2, 0),
        "float32": (4096, 2048, 1792, 8, 4, 512),
        "wide_k": (8192, 16384, 2048, 8, 2, 1024),
        "rows_not_of_128": (640, 256, 384, 2, 2, 0),
    }.items() for kind in moe.KINDS]


def _divides_or_whole(dim, tile):
    return dim % tile == 0 and (tile % 128 == 0 or tile == dim)


@pytest.mark.parametrize("kind,m,k,n,groups,itemsize,rows",
                         _CELL_PRODUCTS + _ODD_PRODUCTS)
def test_a_tile_follows_the_rules(kind, m, k, n, groups, itemsize, rows):
    tm, tk, tn = tile = moe.choose_tile(kind, m, k, n, groups, itemsize,
                                        rows)
    # no masked k tile and no partly empty n tile
    assert _divides_or_whole(k, tk) and _divides_or_whole(n, tn), tile
    assert m % tm == 0 and (tm in moe.ROW_TILES or tm == m), tile
    assert moe._vmem_bytes(kind, tm, tk, tn, itemsize) <= moe.VMEM_BUDGET
    # tk = k (tgmm: the larger out tile) wherever it fits beside the
    # same tm and tn: nothing larger in k or n that fits was passed over
    # for a tile that costs the same or more
    for wider in moe._cuts(k):
        if wider > tk and moe._vmem_bytes(
                kind, tm, wider, tn, itemsize) <= moe.VMEM_BUDGET:
            pytest.fail(f"{tile}: tk {wider} fits and was not taken")
    # the choice is a pure function of what it is given
    assert moe.choose_tile(kind, m, k, n, groups, itemsize, rows) == tile


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_forward_products_of_a_cell_hold_their_weights(cell):
    """With ONE k tile an expert's weights cross HBM once a group and
    not once a visit of a row tile: both forward products and both
    gradients to lhs of every cell take tk = k."""
    m, K, N, wide, held, rows = CELLS[cell]
    for name, kind, k, n in _products(K, N, wide):
        tm, tk, tn = moe.choose_tile(kind, m, k, n, held, 2, rows)
        if kind != "tgmm":
            assert tk == k, (name, tm, tk, tn)
        assert tm <= max(rows, 128), (name, tm)


@pytest.mark.parametrize("kind", moe.KINDS)
def test_the_row_tile_follows_the_rows_a_group_holds(kind):
    """A row tile that straddles a group boundary is visited once a
    group: many rows a group take the large tile, few the small."""
    many = moe.choose_tile(kind, 32768, 1024, 1024, 4, 2, 8192)[0]
    few = moe.choose_tile(kind, 32768, 1024, 1024, 64, 2, 128)[0]
    assert many >= few and few <= 256 <= many, (many, few)


def test_a_dimension_too_large_to_hold_whole_is_cut_and_counted():
    """No multiple of 128 divides 10,007 and a (10007, 10007) block does
    not fit: the tile pads, and `padded_tiles` says so."""
    tm, tk, tn = moe.choose_tile("gmm", 1024, 10007, 10007, 2, 2, 0)
    assert 10007 % tn and tn % 128 == 0
    assert moe._vmem_bytes("gmm", tm, tk, tn, 2) <= moe.VMEM_BUDGET
    before = moe.route_counts()
    moe._tile_of("gmm", 1024, 10007, 10007, 2, 2, 0)
    after = moe.route_counts()
    assert after["padded_tiles"] == before["padded_tiles"] + 1
    assert after["exact_tiles"] == before["exact_tiles"]


def test_an_unknown_kind_is_refused():
    with pytest.raises(moe.MXNetError, match="kind"):
        moe.choose_tile("gmm_t", 512, 128, 128, 2)


@pytest.mark.parametrize("cell", list(CELLS))
def test_no_product_of_a_cell_pads(cell):
    """The cell's whole stage traced (forward and gradient, the kernel
    route, abstract operands at the cell's shapes): every grouped
    product is noted with a tile that divides, `padded_tiles` stays 0."""
    m, K, N, wide, held, rows = CELLS[cell]
    form = "silu_gated" if wide == 2 else "relu2"
    t = 16384 if wide == 2 else 8192
    stage = moe._Stage(form, m, moe.row_block(m), True, rows)
    arg = jax.ShapeDtypeStruct
    operands = (arg((t, K), jnp.bfloat16), arg((m,), jnp.int32),
                arg((m,), jnp.float32), arg((held,), jnp.int32),
                arg((held, K, wide * N), jnp.bfloat16),
                arg((held, N, K), jnp.bfloat16))

    def loss(u, token, weight, sizes, w1, w2):
        return moe._experts(u, token, weight, sizes, w1, w2,
                            stage).astype(jnp.float32).sum()

    def kernel_branch(*operands, tpu, default):
        return tpu(*operands)

    jax.clear_caches()
    before = moe.route_counts()
    with pytest.MonkeyPatch.context() as patch:
        # trace the branch a program lowered for the TPU runs
        patch.setattr(jax.lax, "platform_dependent", kernel_branch)
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 2, 4, 5)))(*operands)
    jax.clear_caches()
    after = moe.route_counts()
    assert after["padded_tiles"] == before["padded_tiles"]
    assert after["exact_tiles"] > before["exact_tiles"]
    seen = moe.tile_choices()
    for name, kind, k, n in _products(K, N, wide):
        tm, tk, tn = seen[kind, m, k, n, held]
        assert k % tk == 0 == n % tn, (name, tm, tk, tn)
        assert (tm, tk, tn) == moe.choose_tile(kind, m, k, n, held, 2, rows)


# rows of each group, then the rows that hold nothing
_GROUPS = {
    "uneven": ([300, 37, 175], 0),
    "an_empty_group": ([200, 0, 184], 128),
    "an_empty_tail": ([100, 60, 96], 256),
    "one_group": ([384], 128),
}


@pytest.mark.parametrize("form", moe.FORMS)
@pytest.mark.parametrize("case", list(_GROUPS))
def test_the_rule_of_the_repo_against_ragged_dot(case, form, monkeypatch):
    """Values and all three gradients of a chunk's two products (the
    activation between them, the rows past the groups masked as the
    stage masks them) through `_gmm` under the Pallas interpreter
    against `lax.ragged_dot`'s own rule; the tiles are small so that a
    product takes several tiles in every dimension."""
    sizes, tail = _GROUPS[case]
    m, k, n = sum(sizes) + tail, 256, 384
    wide = 2 if form == "silu_gated" else 1
    monkeypatch.setattr(moe, "ROW_TILES", (128,))
    monkeypatch.setattr(moe, "_cuts", lambda dim, exact=True: [128])
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    w1 = jnp.asarray(rng.randn(len(sizes), k, wide * n) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.randn(len(sizes), n, k) * 0.1, jnp.float32)
    ct = jnp.asarray(rng.randn(m, k), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    used = (jnp.arange(m) < sum(sizes))[:, None]

    def chunk(product):
        def f(x, w1, w2):
            hidden = jnp.where(used, product(x, w1), 0)
            out = product(moe._activate(hidden, form), w2)
            return (jnp.where(used, out, 0) * ct).sum()
        return jax.value_and_grad(f, argnums=(0, 1, 2))

    got, got_grads = chunk(lambda a, b: moe._gmm(
        a, b, group_sizes, 0, True))(x, w1, w2)
    want, want_grads = chunk(lambda a, b: moe._ragged(
        a, b, group_sizes))(x, w1, w2)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    # a row past the groups: undefined from the kernel, as in the forward
    got_grads = (jnp.where(used, got_grads[0], 0),) + got_grads[1:]
    for g, w in zip(got_grads, want_grads):
        assert float(jnp.abs(w).max()) > 1e-3
        # float32 partial sums in another order: a few ulps of the largest
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))
    seen = moe.tile_choices()
    assert seen["gmm", m, k, wide * n, len(sizes)] == (128, 128, 128)
    assert seen["dlhs", m, wide * n, k, len(sizes)] == (128, 128, 128)
    assert seen["tgmm", m, n, k, len(sizes)] == (128, 128, 128)
