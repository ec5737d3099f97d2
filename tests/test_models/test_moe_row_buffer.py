"""The held experts' row buffer (``models/moe.py``): sized by what a block
of tokens expects for the experts held, the worst case kept as the same code
at the other size, chosen a block from the sizes it counts. Toy widths, CPU
(the grouped products in the Pallas interpreter); the dense float32 block
below is the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.kernels import registry, tile_policy
from magiattention_tpu.kernels.grouped_matmul import grouped_matmul
from magiattention_tpu.models import hybrid, moe

# 64 tokens a block x top 4 of 16 experts, 4 held: 256 pairs at worst, 64
# expected, a buffer of 96 (1.5 x, in 16-row tiles). No other size below is
# 96 or 256, so a shape's leading dimension says whose it is.
SB, K, N_EXPERTS, HELD, DIM, FFN = 64, 4, 16, 4, 32, 24
WORST, TILE = SB * K, 16
CAPACITY = tile_policy.grouped_row_capacity(
    SB * K * HELD / N_EXPERTS, WORST, TILE)
ACTS = ("relu2", "swiglu")


def test_the_toy_sizes_are_what_the_tests_below_count_on():
    assert (CAPACITY, WORST) == (96, 256)
    assert tile_policy.grouped_row_tile(SB * K // N_EXPERTS) == TILE


def _share(seed, act, dtype=jnp.float32, offset=4):
    """A layer that holds experts ``offset .. offset + HELD`` of N_EXPERTS."""
    cfg = hybrid.HybridConfig(
        dim=DIM, n_experts=N_EXPERTS, experts_held=N_EXPERTS, top_k=K,
        expert_ffn=FFN, shared_ffn=16, expert_act=act)
    lyr = hybrid._init_experts(cfg, jax.random.PRNGKey(seed))
    return {**lyr, "w_up": lyr["w_up"][offset:offset + HELD].astype(dtype),
            "w_down": lyr["w_down"][offset:offset + HELD].astype(dtype)}


def _dense_block(h, topi, weights, w_up, w_down, offset, act):
    """Every held expert on every token, float32 at ``highest``; a token
    keeps what its choices weigh."""
    with jax.default_matmul_precision("highest"):
        h, w_up, w_down = (x.astype(jnp.float32) for x in (h, w_up, w_down))
        outs = jnp.einsum("sef,efd->sed", moe._expert_act(
            jnp.einsum("sd,edf->sef", h, w_up), act), w_down)
        chosen = topi[:, :, None] == offset + jnp.arange(w_up.shape[0])
        return jnp.einsum(
            "ske,sk,sed->sd", chosen.astype(jnp.float32), weights, outs)


def _pairs(rows_held: int, offset=4, k=K):
    """Chosen ids ``(SB, k)`` of which exactly ``rows_held`` pairs fall on
    the experts held, spread over them; the others on experts held
    elsewhere."""
    flat = np.arange(SB * k)
    ids = np.where(flat < rows_held, offset + flat % HELD,
                   (offset + HELD + flat % (N_EXPERTS - HELD)) % N_EXPERTS)
    return jnp.asarray(ids.reshape(SB, k), jnp.int32)


def _block_inputs(seed, act, dtype, rows_held, k=K):
    lyr = _share(seed, act, dtype)
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 3)
    h = jax.random.normal(ks[0], (SB, DIM)).astype(dtype)
    weights = jax.random.uniform(ks[1], (SB, k), jnp.float32, 0.1, 1.0)
    dy = jax.random.normal(ks[2], (SB, DIM)).astype(dtype)
    return h, _pairs(rows_held, k=k), weights, lyr["w_up"], lyr["w_down"], dy


@pytest.mark.parametrize("act", ACTS)
def test_a_block_at_the_expected_buffer_equals_itself_at_the_worst_case(act):
    """(a) Output, ``d h``, ``d weights``, ``d w_up``, ``d w_down`` in bf16,
    bit for bit: a live row sits in the same row tile at either size, the
    products visit live tiles only, and a pair past the buffer adds an exact
    zero both ways."""
    h, topi, weights, w_up, w_down, dy = _block_inputs(
        0, act, jnp.bfloat16, rows_held=CAPACITY - 7)
    sizes = moe.held_expert_rows(topi, HELD, 4)

    def at(capacity):
        def block(h, weights, w_up, w_down):
            return moe._held_experts_block(
                h, topi, weights, w_up, w_down, sizes, offset=4,
                tile_rows=TILE, act=act, capacity=capacity)
        y, vjp = jax.vjp(block, h, weights, w_up, w_down)
        return (y, *vjp(dy))

    tight, worst = jax.jit(at, static_argnums=0)(CAPACITY), jax.jit(
        at, static_argnums=0)(WORST)
    assert float(jnp.abs(tight[0].astype(jnp.float32)).max()) > 0
    for name, a, b in zip(("y", "dh", "dweights", "dw_up", "dw_down"),
                          tight, worst):
        np.testing.assert_array_equal(a, b, err_msg=name)


def _block_token_major(h, topi, weights, w_up, w_down, sizes, *, offset,
                       tile_rows, act, capacity):
    """The block with its old, token-major way back: every pair's row of
    the buffer gathered back to ``[tokens x k, dim]``, viewed as ``[tokens,
    k, dim]`` and weighed by one ``einsum``; plain gathers, whose transposes
    autodiff takes."""
    sb, k = topi.shape
    mine, gid = moe._local_expert_ids(topi, w_up.shape[0], offset)
    order = jnp.argsort(gid.reshape(-1), stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(sb * k, dtype=order.dtype))
    live = (jnp.arange(capacity) < jnp.sum(sizes))[:, None]
    rows = jnp.where(live, h[order[:capacity] // k], 0)
    up = grouped_matmul(rows, w_up, sizes, tile_rows=tile_rows)
    inner = jnp.where(live, moe._expert_act(up, act), 0).astype(h.dtype)
    out = jnp.where(live, grouped_matmul(
        inner, w_down, sizes, tile_rows=tile_rows, out_dtype=h.dtype), 0)
    back = out[jnp.minimum(inverse, capacity - 1)].reshape(sb, k, -1)
    gate = jnp.where(mine, weights, 0.0).astype(h.dtype)
    return jnp.einsum("sk,skd->sd", gate, back,
                      preferred_element_type=jnp.float32).astype(h.dtype)


@pytest.mark.parametrize("k", [4, 6, 8])
@pytest.mark.parametrize("act", ACTS)
@pytest.mark.parametrize("buffer, rows_held", [
    pytest.param("expected", "fit", id="expected-pairs_past_it"),
    pytest.param("worst", "fit", id="worst-held_elsewhere"),
    pytest.param("worst", "worst", id="worst-every_pair_held"),
])
def test_the_way_back_in_the_buffers_order_equals_the_token_major_einsum(
        k, act, buffer, rows_held):
    """Output, ``d h``, ``d weights``, ``d w_up``, ``d w_down`` of the block
    equal those of the token-major form to float32 rounding, at k = 4, 6, 8:
    at the expected buffer with pairs past it, at the worst case with the
    same pairs (on experts held elsewhere at its tail), and at the worst case
    with every pair on the experts held."""
    worst, tile = SB * k, tile_policy.grouped_row_tile(SB * k // N_EXPERTS)
    expected = tile_policy.grouped_row_capacity(
        worst * HELD / N_EXPERTS, worst, tile)
    assert expected < worst
    capacity = expected if buffer == "expected" else worst
    h, topi, weights, w_up, w_down, dy = _block_inputs(
        4, act, jnp.float32, expected - 3 if rows_held == "fit" else worst, k)
    sizes = moe.held_expert_rows(topi, HELD, 4)

    @jax.jit
    def both(h, weights, w_up, w_down, dy):
        def at(fn):
            y, vjp = jax.vjp(lambda h, weights, w_up, w_down: fn(
                h, topi, weights, w_up, w_down, sizes, offset=4,
                tile_rows=tile, act=act, capacity=capacity),
                h, weights, w_up, w_down)
            return (y, *vjp(dy))
        return at(moe._held_experts_block), at(_block_token_major)

    got, want = both(h, weights, w_up, w_down, dy)
    for name, a, b in zip(("y", "dh", "dweights", "dw_up", "dw_down"),
                          got, want):
        scale = float(jnp.abs(b).max())
        assert scale > 0, name
        np.testing.assert_allclose(a, b, atol=1e-6 * scale, rtol=0,
                                   err_msg=name)


@pytest.fixture()
def marked_worst_case(monkeypatch):
    """The block at the worst case adds 1000 to its output: what came out
    says which size ran."""
    block = moe._held_experts_block
    monkeypatch.setattr(
        moe, "_held_experts_block", lambda *a, capacity, **kw: block(
            *a, capacity=capacity, **kw) + 1000.0 * (capacity == WORST))


@pytest.mark.parametrize("act", ACTS)
def test_a_block_that_just_fits_and_one_row_more(act, marked_worst_case):
    """(c) ``sum(sizes) == capacity`` runs at the expected buffer — its last
    row is live, and the pairs past the buffer read it at weight 0 —, one
    row more runs at the worst case; output and every gradient are the
    dense block's either way (the mark's gradient is 0)."""
    tiered = moe._tiered_experts_block(
        CAPACITY, WORST, offset=4, tile_rows=TILE, act=act)

    @jax.jit
    def both(h, topi, weights, w_up, w_down, dy):
        def block(h, weights, w_up, w_down):
            return tiered(h, topi, weights, w_up, w_down,
                          moe.held_expert_rows(topi, HELD, 4))
        y, vjp = jax.vjp(block, h, weights, w_up, w_down)
        ref, ref_vjp = jax.vjp(
            lambda *a: _dense_block(a[0], topi, *a[1:], 4, act),
            h, weights, w_up, w_down)
        return (y, *vjp(dy)), (ref, *ref_vjp(dy))

    for rows_held, mark in ((CAPACITY, 0.0), (CAPACITY + 1, 1000.0),
                            (WORST, 1000.0), (0, 0.0)):
        got, want = both(*_block_inputs(1, act, jnp.float32, rows_held))
        got = (got[0] - mark, *got[1:])
        for name, a, b in zip(("y", "dh", "dweights", "dw_up", "dw_down"),
                              got, want):
            scale = float(jnp.abs(b).max()) or 1.0
            np.testing.assert_allclose(
                a, b, atol=2e-4 * scale, rtol=0,
                err_msg=f"{name} at {rows_held} rows")


def _rigged(h, lyr, block: int, offset=4):
    """``h`` with the tokens of one block solved for router logits of +4 on
    the experts held and -4 on the others (the router is 32 x 16, of full
    rank), so that all ``K`` choices of each fall on the experts held."""
    held = (jnp.arange(N_EXPERTS) >= offset) & (
        jnp.arange(N_EXPERTS) < offset + HELD)
    aimed = jnp.where(held, 4.0, -4.0) @ jnp.linalg.pinv(lyr["router"])
    start = block * SB
    return h.at[start:start + SB].set(aimed + 0.01 * h[start:start + SB])


@pytest.mark.parametrize("act", ACTS)
def test_a_rigged_block_takes_the_worst_case_and_drops_no_row(act):
    """(b), (f) Three blocks of tokens; then the same with every choice of
    the middle block's tokens on the experts held: that block alone does not
    fit, runs at the worst case, and the layer still equals the dense
    reference with every routed row taken."""
    lyr = _share(2, act)
    kw = dict(top_k=K, scale=2.5, expert_offset=4, token_block=SB, act=act)
    layer = jax.jit(lambda h: moe.dropless_moe_ffn(h, lyr, **kw))
    plain = jax.random.normal(jax.random.PRNGKey(7), (3 * SB, DIM))
    for h, fitted in ((plain, 3), (_rigged(plain, lyr, 1), 2)):
        y, routes = layer(h)
        assert registry.last_choice("moe_row_buffer") == "rows96of256"
        block_rows = np.asarray(routes["block_rows"])
        assert int(routes["blocks_fitted"]) == fitted, block_rows
        assert (block_rows <= CAPACITY).sum() == fitted
        if fitted == 2:
            assert block_rows[1] == WORST
        # no row dropped: the grouped products took every pair routed here
        mine = (routes["topi"] >= 4) & (routes["topi"] < 4 + HELD)
        assert int(routes["group_rows"].sum()) == int(mine.sum())
        assert int(block_rows.sum()) == int(mine.sum())
        topi, weights, _ = moe.route_sigmoid_topk(
            h, lyr["router"], lyr["e_bias"], K, 2.5)
        np.testing.assert_array_equal(topi, routes["topi"])
        with jax.default_matmul_precision("highest"):
            shared = moe._expert_act(h @ lyr["ws_up"], act) @ lyr["ws_down"]
        want = shared + _dense_block(
            h, topi, weights, lyr["w_up"], lyr["w_down"], 4, act)
        assert float(jnp.linalg.norm(y - want) / jnp.linalg.norm(want)) < 1e-5


def _eqns(jaxpr, name):
    """Every equation of primitive ``name`` in ``jaxpr`` and below it, the
    kernels' bodies apart (a ``pl.when`` is a ``cond`` too)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub, name)


def _layer_grad_jaxpr(lyr, act, tokens=2 * SB):
    kw = dict(top_k=K, scale=2.5, expert_offset=0, token_block=SB, act=act)

    def loss(h, lyr):
        layer = jax.checkpoint(
            lambda h, lyr: moe.dropless_moe_ffn(h, lyr, **kw)[0],
            policy=moe.ROUTES_SAVED)
        return layer(h, lyr).astype(jnp.float32).sum()

    h = jnp.ones((tokens, DIM), jnp.bfloat16)
    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, lyr).jaxpr


@pytest.mark.parametrize("act", ACTS)
def test_no_branch_and_no_scan_carries_a_row_buffer(act):
    """(e) Under ``jax.checkpoint(policy=ROUTES_SAVED)`` and ``jax.grad``:
    a ``cond``'s operands and results are block-shaped (tokens, or an
    expert's weights), never ``[capacity, ..]`` or ``[tokens x top_k, ..]``
    — a ``jax.checkpoint`` round the ``cond`` would hand the backward's
    ``cond`` the union of both branches' residuals, the worst case's
    zero-filled — and the loops over the blocks stack no such array."""
    lyr = _share(3, act, offset=0)
    jaxpr = _layer_grad_jaxpr(lyr, act)
    conds = list(_eqns(jaxpr, "cond"))
    # the forward, its copy in the layer's re-forward, the backward
    assert len(conds) >= 2, len(conds)
    for eqn in conds:
        for var in (*eqn.invars, *eqn.outvars):
            shape = var.aval.shape
            assert not shape or shape[0] not in (CAPACITY, WORST), (
                eqn.primitive, shape)
        assert {v.aval.shape[:1] for v in eqn.outvars} <= {
            (SB,), (HELD,)}, [v.aval.shape for v in eqn.outvars]
    scans = list(_eqns(jaxpr, "scan"))
    assert scans
    for eqn in scans:
        for var in (*eqn.invars, *eqn.outvars):  # [blocks,] rows, width
            shape = var.aval.shape
            assert len(shape) < 2 or not (
                {CAPACITY, WORST} & set(shape[:2])), shape


def test_a_chip_that_holds_every_expert_traces_no_branch():
    """(d) ``held == n_experts`` expects the worst case: one size, no
    ``cond``, the program of before."""
    cfg = hybrid.HybridConfig(
        dim=DIM, n_experts=N_EXPERTS, experts_held=N_EXPERTS, top_k=K,
        expert_ffn=FFN, shared_ffn=16)
    lyr = hybrid._init_experts(cfg, jax.random.PRNGKey(0))
    jaxpr = _layer_grad_jaxpr(lyr, "relu2")
    assert not list(_eqns(jaxpr, "cond"))
    assert list(_eqns(jaxpr, "scan"))
    assert registry.last_choice("moe_row_buffer") == "rows256of256"
