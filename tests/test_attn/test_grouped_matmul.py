"""``kernels/grouped_matmul.py``: the three products (forward, ``d rows``,
``dW``) against a loop over the groups in float32, at toy widths under the
interpreter, a handful of grid steps a case."""

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.kernels import grouped_matmul as gm
from magiattention_tpu.kernels import registry, tile_policy

# name: (rows M, K, N, row tile, group sizes)
CASES = {
    "groups_end_on_tile_edges": (96, 64, 32, 16, (16, 32, 16)),
    "a_boundary_inside_a_tile": (96, 64, 32, 16, (10, 22, 5)),
    "a_group_with_no_rows": (96, 64, 32, 16, (16, 0, 20, 0)),
    "no_live_row_at_all": (64, 64, 32, 16, (0, 0, 0)),
    "every_row_live": (64, 64, 32, 16, (30, 2, 32)),
    "sizes_no_multiple_of_8": (100, 64, 32, 16, (7, 13, 3, 9, 21)),
    # 2688 : 1856 = 42 : 29, and a row count no multiple of the tile
    "the_cells_ratio_at_toy_widths": (150, 168, 116, 32, (40, 0, 33, 50)),
    # K a multiple of the lanes and N none: the bodies are given each
    # group's weight transposed (tile_policy.grouped_weight_k_minor)
    "a_weight_kept_k_minor": (100, 128, 40, 16, (10, 0, 22, 5)),
}


def _oracle(rows, w, dy, sizes):
    """A loop over the groups in float32; zeros past the groups."""
    rows, w, dy = (np.asarray(a, np.float32) for a in (rows, w, dy))
    out, d_rows, dw = np.zeros_like(dy), np.zeros_like(rows), np.zeros_like(w)
    start = 0
    for g, size in enumerate(sizes):
        sl = slice(start, start + size)
        out[sl] = rows[sl] @ w[g]
        d_rows[sl] = dy[sl] @ w[g].T
        dw[g] = rows[sl].T @ dy[sl]
        start += size
    return {"out": out, "d_rows": d_rows, "dW": dw}


@lru_cache(maxsize=None)
def _products(case: str, past: float = 0.0):
    """``(kernel's, oracle's)`` three products of a case; the rows and the
    cotangent past the groups hold ``past``."""
    m, k, n, tile, sizes = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    live = (np.arange(m) < sum(sizes))[:, None]
    rows = jnp.where(live, jax.random.normal(keys[0], (m, k)), past).astype(
        jnp.bfloat16)
    w = jax.random.normal(keys[1], (len(sizes), k, n)).astype(jnp.bfloat16)
    dy = jnp.where(live, jax.random.normal(keys[2], (m, n)), past).astype(
        jnp.bfloat16)

    @jax.jit
    def run(rows, w, dy, group_sizes):
        out, vjp = jax.vjp(
            lambda r, w: gm.grouped_matmul(r, w, group_sizes, tile_rows=tile),
            rows, w)
        d_rows, dw = vjp(dy.astype(out.dtype))
        return out, d_rows, dw

    got = dict(zip(("out", "d_rows", "dW"), run(
        rows, w, dy, jnp.asarray(sizes, jnp.int32))))
    return got, _oracle(jnp.where(live, rows, 0), w, jnp.where(live, dy, 0),
                        sizes), sum(sizes)


@pytest.mark.parametrize("product", ["out", "d_rows", "dW"])
@pytest.mark.parametrize("case", CASES)
def test_a_product_agrees_with_a_loop_over_the_groups(case, product):
    got, want, live = _products(case)
    got, want = got[product], want[product]
    assert got.dtype == (jnp.float32 if product == "out" else jnp.bfloat16)
    got = np.asarray(got, np.float32)
    if product != "dW":  # past the groups the contract promises nothing
        got, want = got[:live], want[:live]
    # float32 out of the MXU; d rows and dW rounded to bf16 once
    tol = 1e-5 if product == "out" else 2.0 ** -8
    scale = max(float(np.abs(want).max(initial=0.0)), 1.0)
    np.testing.assert_allclose(got, want, atol=tol * scale, rtol=0)


@pytest.mark.parametrize("case", [
    "a_boundary_inside_a_tile", "a_group_with_no_rows",
    "the_cells_ratio_at_toy_widths", "a_weight_kept_k_minor"])
def test_nothing_past_the_groups_is_read_into_a_result(case):
    """The contract of the rows past the groups: whatever they hold, NaN
    included, the live rows of ``out`` and ``d rows`` and all of ``dW`` are
    what they are with zeros there."""
    poisoned, _, live = _products(case, past=float("nan"))
    clean, _, _ = _products(case)
    for product in ("out", "d_rows", "dW"):
        stop = None if product == "dW" else live
        np.testing.assert_array_equal(
            np.asarray(poisoned[product][:stop], np.float32),
            np.asarray(clean[product][:stop], np.float32))


def test_a_group_with_no_rows_gets_a_dw_of_exact_zeros():
    got, _, _ = _products("a_group_with_no_rows")
    dw = np.asarray(got["dW"], np.float32)
    assert not dw[1].any() and not dw[3].any()
    assert dw[0].any() and dw[2].any()
    none, _, _ = _products("no_live_row_at_all")
    assert not np.asarray(none["dW"], np.float32).any()


def test_the_down_products_type_is_the_accumulator_rounded_once():
    m, k, n, tile, sizes = CASES["a_boundary_inside_a_tile"]
    rows = jax.random.normal(jax.random.PRNGKey(0), (m, k)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, k, n)).astype(jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    wide = gm.grouped_matmul(rows, w, sizes, tile_rows=tile)
    narrow = gm.grouped_matmul(
        rows, w, sizes, tile_rows=tile, out_dtype=jnp.bfloat16)
    assert narrow.dtype == jnp.bfloat16
    live = int(sizes.sum())
    np.testing.assert_array_equal(
        np.asarray(narrow[:live], np.float32),
        np.asarray(wide[:live].astype(jnp.bfloat16), np.float32))


@pytest.mark.parametrize("sizes, tile, visits, fill", [
    # 0-16 | 16-48 | 48-64: four whole tiles
    ((16, 32, 16), 16, 4, 1.0),
    # 0-10 in tile 0; 10-32 in tiles 0, 1; 32-37 in tile 2
    ((10, 22, 5), 16, 4, 37 / 64),
    ((16, 0, 20, 0), 16, 1 + 2, 36 / 48),
    ((0, 0, 0), 16, 0, 1.0),
    # 384 rows a group, boundaries anywhere: 3 to 4 tiles of 128 each
    ((380, 390, 382), 128, 3 + 4 + 4, 1152 / (11 * 128)),
])
def test_tile_stats_against_a_count_by_hand(sizes, tile, visits, fill):
    assert gm.tile_stats(sizes, tile) == (visits, pytest.approx(fill))
    # the grid's own count, from the tables the kernel is given
    *_, count = gm.visit_tables(
        jnp.asarray(sizes, jnp.int32), 4 * sum(sizes) + tile, tile, False)
    assert int(count) == visits


def test_the_visit_tables_by_hand():
    """Sizes (10, 0, 22, 5) on 16-row tiles of a 64-row buffer: group 0 in
    tile 0, group 2 in tiles 0 and 1, group 3 in tile 2; the empty group is
    visited (tile 0, nothing to add) only where dW must write its zeros."""
    sizes = jnp.asarray((10, 0, 22, 5), jnp.int32)
    group_of, tile_of, spans, visits = gm.visit_tables(sizes, 64, 16, False)
    assert int(visits) == 4
    assert group_of.shape == tile_of.shape == (4 + 4 - 1,)  # 1-D, worst case
    np.testing.assert_array_equal(group_of[:4], [0, 2, 2, 3])
    np.testing.assert_array_equal(tile_of[:4], [0, 0, 1, 2])
    np.testing.assert_array_equal(spans, [0, 10, 10, 32, 37])
    group_of, tile_of, _, visits = gm.visit_tables(sizes, 64, 16, True)
    assert int(visits) == 5
    np.testing.assert_array_equal(group_of[:5], [0, 1, 2, 2, 3])
    np.testing.assert_array_equal(tile_of[:5], [0, 0, 0, 1, 2])
    assert int(tile_of.max()) <= 3 and int(group_of.max()) <= 3


def test_which_weights_are_kept_k_minor():
    """The cell's up projection (2688 x 1856: 1856 is 14.5 lanes) is, its
    down projection and every toy case but the one made for it are not."""
    assert tile_policy.grouped_weight_k_minor(2688, 1856)
    assert not tile_policy.grouped_weight_k_minor(1856, 2688)
    assert not tile_policy.grouped_weight_k_minor(1856, 1856)
    assert [name for name, (_, k, n, _, _) in CASES.items()
            if tile_policy.grouped_weight_k_minor(k, n)] == [
        "a_weight_kept_k_minor"]


def test_the_tiles_are_rules_over_static_shapes():
    """The cell's: 8192 tokens x top 6 of 128 experts expect 384 rows a
    group, which a 128-row tile gives 3 whole tiles; its two weights fit
    three column blocks each, lane-aligned and evened out."""
    assert tile_policy.grouped_row_tile(8192 * 6 // 128) == 128
    assert tile_policy.grouped_row_tile(18) == 16
    assert tile_policy.grouped_row_tile(10 ** 6) == 512
    assert tile_policy.grouped_col_tile(2688, 1856, 2) == 640
    assert tile_policy.grouped_col_tile(1856, 2688, 2) == 896
    assert tile_policy.grouped_col_tile(64, 32, 2) == 32  # all of it
    for k, n in ((2688, 1856), (1856, 2688)):
        tk, tn = tile_policy.grouped_dw_tiles(k, n, 2)
        assert tk % 128 == 0 and tn % 128 == 0
        assert tk * tn * (4 + 2 * 2) <= tile_policy.GROUPED_BLOCK_BUDGET
    assert tile_policy.grouped_dw_tiles(64, 32, 2) == (64, 32)


@pytest.mark.parametrize("tokens, top_k, held, n_experts, want", [
    (8192, 8, 32, 128, 24576),   # trinitymini.longdocs32k.cp1: of 65536
    (8192, 6, 32, 128, 18432),   # nemotron3nano.packed32k.cp1: of 49152
    (8192, 6, 128, 128, 49152),  # every expert held: the worst case
    (8192, 6, 96, 128, 49152),   # a margin that reaches it
    (256, 6, 8, 32, 576),        # the toy's: 384 expected, 16-row tiles
    (100, 6, 8, 32, 240),        # 150 expected -> 225 -> whole 16-row tiles
    (10, 3, 1, 8, 16),           # 3.75 expected -> 6 -> one tile
    (4, 3, 1, 8, 12),            # one tile is more than the worst case
])
def test_the_row_buffer_is_a_rule_over_what_a_block_expects(
        tokens, top_k, held, n_experts, want):
    """``GROUPED_ROW_MARGIN`` (1.5) times the pairs expected for the experts
    held, in whole row tiles, never above ``tokens x top_k``."""
    worst = tokens * top_k
    tile = tile_policy.grouped_row_tile(worst // n_experts)
    expected = worst * held / n_experts
    got = tile_policy.grouped_row_capacity(expected, worst, tile)
    assert got == want
    assert expected <= got <= worst
    assert got == worst or got % tile == 0
    assert (got == worst) == (
        held == n_experts or expected * tile_policy.GROUPED_ROW_MARGIN
        > worst - tile)


def test_the_default_row_tile_is_the_rules_for_an_even_share():
    """Without ``tile_rows`` the rows a group expects are ``M / G``."""
    rows = jnp.ones((96, 64), jnp.bfloat16)
    w = jnp.ones((3, 64, 32), jnp.bfloat16)
    out = gm.grouped_matmul(rows, w, jnp.asarray((30, 30, 30), jnp.int32))
    np.testing.assert_array_equal(np.asarray(out[:90]), 64.0)


def test_operands_that_make_no_grouped_product_are_refused():
    rows, w = jnp.ones((32, 64)), jnp.ones((3, 48, 32))
    with pytest.raises(ValueError, match="grouped product"):
        gm.grouped_matmul(rows, w, jnp.zeros((3,), jnp.int32))
    with pytest.raises(ValueError, match="grouped product"):
        gm.grouped_matmul(rows, jnp.ones((3, 64, 32)), jnp.zeros((4,), jnp.int32))


def test_the_registry_knows_one_backend_and_no_pin():
    assert registry.backends_for("moe_grouped") == ("pallas_grouped",)
    assert registry.PIN_KEYS["moe_grouped"] == ()
    assert registry.PIN_KEYS["moe_row_buffer"] == ()  # a rule, no key


@pytest.mark.parametrize("row_buffer, sized", [
    (None, {}),
    (48, {"row_buffer": 48, "fitted": True}),
    (37, {"row_buffer": 37, "fitted": True}),
    (36, {"row_buffer": 36, "fitted": False}),
])
def test_telemetry_is_told_a_plans_tile_stats_and_only_when_on(
        monkeypatch, tmp_path, row_buffer, sized):
    """Gated and observing: with telemetry off nothing is traced into the
    program; on, one ``grouped_matmul_plan`` record a plan, with the row
    buffer the caller sized and whether the plan's rows fitted it."""
    from magiattention_tpu import telemetry

    sizes = jnp.asarray((10, 22, 5), jnp.int32)
    note = jax.jit(
        lambda s: (gm.note_tile_stats(s, 16, row_buffer), s.sum())[1])
    monkeypatch.delenv("MAGI_ATTENTION_TELEMETRY", raising=False)
    assert "callback" not in str(jax.make_jaxpr(note)(sizes))
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path))
    telemetry.reset()
    try:
        jax.block_until_ready(jax.jit(lambda s: (
            gm.note_tile_stats(s, 16, row_buffer), s.sum())[1])(sizes))
        jax.effects_barrier()
        record = telemetry.get_collector().last_event["grouped_matmul_plan"]
    finally:
        telemetry.reset()
    assert (record["tile_visits"], record["live_rows"]) == (4, 37)
    assert record["tile_fill"] == pytest.approx(37 / 64)
    assert {k: record.get(k) for k in ("row_buffer", "fitted")} == {
        "row_buffer": None, "fitted": None, **sized}
