"""The chunked state-space scan (``kernels/ssd.py``), forward and backward,
against the recurrence it computes, one token at a time, on documents whose
boundaries fall inside, on and beside the chunk's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.kernels import registry, ssd


def recurrence(x, dt, a, b, c, starts):
    """``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t`` with ``h = 0`` before
    a document's first token; ``y_t = C_t h_t``. float32, every head."""
    t, heads, p = x.shape
    rep = heads // b.shape[1]
    bh, ch = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)
    first = jnp.arange(t) == starts

    def step(h, inp):
        x_t, dt_t, b_t, c_t, f = inp
        h = jnp.where(f, 0.0, h)
        h = jnp.exp(dt_t * a)[:, None, None] * h + (
            (dt_t[:, None] * x_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.einsum("hn,hnp->hp", c_t, h)

    _, y = jax.lax.scan(
        step, jnp.zeros((heads, b.shape[-1], p)), (x, dt, bh, ch, first))
    return y


def make(lens, heads=8, p=64, groups=2, n=32, seed=0):
    t = sum(lens)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (t, groups, n)) * 0.3
    c = jax.random.normal(ks[4], (t, groups, n)) * 0.3
    starts = np.repeat(np.cumsum([0] + lens[:-1]), lens).astype(np.int32)
    return (x, dt, a, b, c), jnp.asarray(starts)


def rel(got, ref):
    return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))


LAYOUTS = {
    "1-127-128-129-300": [1, 127, 128, 129, 300],  # 685: padded to 768
    "one-document": [256],
    "boundary-on-the-chunk": [128, 128],
    "many-short": [3, 5, 2, 60, 1, 1, 56],
}


# (layout, heads, groups, the bound lowered to this many heads' step): the
# layouts at two groups of four heads, one grid step a group; a group of
# four over two grid steps; groups of a single head
CASES = [pytest.param(lens, 8, 2, None, id=name)
         for name, lens in LAYOUTS.items()] + [
    pytest.param(LAYOUTS["1-127-128-129-300"], 8, 2, 2,
                 id="two-grid-steps-a-group"),
    pytest.param(LAYOUTS["1-127-128-129-300"], 4, 4, None,
                 id="one-head-a-group"),
]


def heads_a_step(heads, groups, lowered, monkeypatch, p=64, n=32):
    """The heads a grid step takes, the rule's bound lowered to ``lowered``
    heads of the cases' shape where it is given."""
    if lowered:
        monkeypatch.setattr(ssd, "STEP_WORDS", lowered * (
            3 * ssd.CHUNK ** 2 + (ssd.CHUNK + n) * (ssd.CHUNK + p)))
    return lowered or heads // groups


@pytest.mark.parametrize("lens,heads,groups,lowered", CASES)
def test_forward_is_the_recurrence(lens, heads, groups, lowered, monkeypatch):
    hb = heads_a_step(heads, groups, lowered, monkeypatch)
    args, starts = make(lens, heads=heads, groups=groups)
    y = ssd.ssd_scan(*args, ssd.segment_rows(starts))
    assert y.shape == args[0].shape
    # the kernel's matmuls take bf16 operands: one rounding is 2e-3
    assert rel(y, recurrence(*args, starts)) < 5e-3
    assert registry.last_choice("ssd") == "pallas_chunked"
    assert registry.last_choice("ssd_heads") == f"heads{hb}"
    assert registry.last_source("ssd_heads") == "shape_rule"


@pytest.mark.parametrize("lens,heads,groups,lowered", CASES)
def test_backward_is_the_recurrences_gradient(
        lens, heads, groups, lowered, monkeypatch):
    hb = heads_a_step(heads, groups, lowered, monkeypatch)
    args, starts = make(lens, heads=heads, groups=groups, seed=1)
    seg = ssd.segment_rows(starts)
    weight = jax.random.normal(jax.random.PRNGKey(7), args[0].shape)
    got = jax.grad(
        lambda *a: jnp.sum(ssd.ssd_scan(*a, seg) * weight),
        argnums=(0, 1, 2, 3, 4))(*args)
    ref = jax.grad(
        lambda *a: jnp.sum(recurrence(*a, starts) * weight),
        argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, r in zip(("x", "dt", "a", "b", "c"), got, ref):
        assert g.shape == r.shape
        # bf16 operands; ``a``'s gradient is 8 numbers, each a sum with
        # cancellation over every token
        assert rel(g, r) < (2e-2 if name == "a" else 6e-3), name
    assert registry.last_choice("ssd_heads") == f"heads{hb}"


@pytest.mark.parametrize("heads,p,n,hb", [
    (8, 64, 128, 8),     # the hybrid cell's group: one step
    (16, 64, 128, 8),    # wider than the bound: two steps a group
    (128, 64, 128, 8),   # one group of 128 heads: sixteen steps
    (80, 64, 128, 10),   # one group of 80
    (12, 64, 128, 12),
    (8, 256, 256, 4),    # wide heads and states: fewer a step
    (30, 64, 128, 10),   # 15 would fit, but 960 lanes are no whole vregs
    (1, 64, 128, 1),     # a single head, 64 lanes, is the whole group
])
def test_heads_a_grid_step(heads, p, n, hb):
    assert ssd.heads_per_step(heads, ssd.CHUNK, p, n) == hb


def test_a_document_start_cuts_the_state_and_nothing_else():
    """Two documents scanned together equal each scanned alone."""
    args, starts = make([100, 156], seed=2)
    both = ssd.ssd_scan(*args, ssd.segment_rows(starts))
    x, dt, a, b, c = args
    alone = ssd.ssd_scan(
        x[100:], dt[100:], a, b[100:], c[100:],
        ssd.segment_rows(jnp.zeros(156, jnp.int32)))
    # (the chunks fall elsewhere in it: other bf16 roundings)
    assert rel(both[100:], alone) < 5e-3
    # and without the boundary the second document sees the first
    merged = ssd.ssd_scan(*args, ssd.segment_rows(jnp.zeros(256, jnp.int32)))
    assert rel(merged[100:], alone) > 1e-2


def test_segment_rows():
    starts = jnp.asarray(np.repeat([0, 100, 130], [100, 30, 126]))
    rows = np.asarray(ssd.segment_rows(starts))
    assert rows.shape == (ssd.SEG_ROWS, 256)
    np.testing.assert_array_equal(rows[0], np.asarray(starts))
    # the first chunk has nothing before it; the second chunk's carried
    # state reaches the tokens of the document that row 127 is in (100..129)
    assert not rows[1, :128].any()
    assert rows[1, 128:130].all() and not rows[1, 130:].any()


def test_heads_must_divide_into_groups():
    (x, dt, a, b, c), starts = make([128], heads=6, groups=4)
    with pytest.raises(ValueError, match="6 heads do not divide"):
        ssd.ssd_scan(x, dt, a, b, c, ssd.segment_rows(starts))
