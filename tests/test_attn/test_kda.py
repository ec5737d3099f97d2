"""``kernels/kda.py:kda_scan`` against the gated delta rule run token by
token (``cellbench/reference_solar_open2.recurrence``, float32), forward and
the gradients of q, k, v, g and beta, in the CPU interpreter.

The cases are what the chunked WY form can get wrong: documents that start
inside a chunk (the carried state must stop there), a length that is no
multiple of the chunk (inert padding), beta near 2 (the update's eigenvalue
near -1, where a Neumann series cancels catastrophically) and a channel whose
decay is -30 a token (``exp(-G)`` of a factorised form leaves float32 within
a chunk). None may give a NaN."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import reference_solar_open2
from magiattention_tpu.kernels import kda, registry, ssd

D = 128
NAMES = ("out", "dq", "dk", "dv", "dg", "dbeta")
# o, dq, dk, dv are written in bf16 (one rounding, 1.6e-3 rms relative);
# g's and beta's gradients are float32 out of a float32 kernel
TOLS = {"out": 4e-3, "dq": 4e-3, "dk": 4e-3, "dv": 4e-3, "dg": 1e-4,
        "dbeta": 1e-4}
CASES = {
    # starts at 40 and 100 inside the first two chunks, T = 150 = 2 * 64 + 22
    "resets_in_chunks_ragged_tail": dict(lens=(40, 60, 50), heads=2),
    # one document over three chunks, beta within 1e-3 of 2, one key channel
    # of every head decaying by -30 a token
    "beta_near_two_steep_decay": dict(lens=(192,), heads=1, beta2=True,
                                      steep=True),
}
# eight heads: the rule takes four a grid step, two groups; two chunks, a
# document start at 40 inside the first
GROUPED = dict(lens=(40, 88), heads=8)


def _inputs(lens, heads, beta2=False, steep=False, seed=0):
    t = sum(lens)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (t, heads, D))).astype(jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (t, heads, D))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (t, heads, D)).astype(jnp.bfloat16)
    g = -jnp.exp(jax.random.uniform(ks[3], (t, heads, D), minval=-5.0,
                                    maxval=1.0))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (t, heads)))
    if beta2:
        beta = 2.0 - 1e-3 * jax.random.uniform(ks[4], (t, heads))
    if steep:
        g = g.at[:, :, 5].set(-30.0)
    do = jax.random.normal(ks[5], (t, heads, D)).astype(jnp.bfloat16)
    cu = np.cumsum([0, *lens])
    starts = np.repeat(cu[:-1], np.diff(cu)).astype(np.int32)
    return (q, k, v, g, beta), do, starts


@jax.jit
def _scan_vjp(q, k, v, g, beta, do, rows):
    """The output and the five gradients of :data:`NAMES`; one compile a
    shape, shared by the tests of this file."""
    out, pull = jax.vjp(lambda *a: kda.kda_scan(*a, rows), q, k, v, g, beta)
    return (out, *pull(do))


@functools.lru_cache(maxsize=None)
def _case(name):
    """``(program, reference)``, each ``{name: array}`` of :data:`NAMES`."""
    (q, k, v, g, beta), do, starts = _inputs(**CASES[name])
    rows = ssd.segment_rows(jnp.asarray(starts), chunk=kda.CHUNK)
    got = dict(zip(NAMES, _scan_vjp(q, k, v, g, beta, do, rows)))
    ref = reference_solar_open2.timed(
        q, k, v, g, beta, do, jnp.asarray(starts == np.arange(len(starts))))
    return jax.device_get(got), jax.device_get(ref)


def _rel(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return np.linalg.norm(got - ref) / np.linalg.norm(ref)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_form_is_the_recurrence(case, name):
    got, ref = _case(case)
    assert got[name].shape == ref[name].shape
    assert np.isfinite(np.asarray(got[name], np.float32)).all()
    assert _rel(got[name], ref[name]) < TOLS[name], (case, name)


def test_the_state_stops_at_a_document_start_inside_a_chunk():
    """A document's first token and what follows it see nothing of the
    document before: the output from the start at 40 on is the output of
    that document alone."""
    case = CASES["resets_in_chunks_ragged_tail"]
    (q, k, v, g, beta), _, _ = _inputs(**case)
    whole = _case("resets_in_chunks_ragged_tail")[0]["out"]
    alone = kda.kda_scan(
        *(x[40:100] for x in (q, k, v, g, beta)),
        ssd.segment_rows(jnp.zeros((60,), jnp.int32), chunk=kda.CHUNK))
    np.testing.assert_allclose(
        np.asarray(whole[40:100], np.float32), np.asarray(alone, np.float32),
        rtol=1e-2, atol=1e-3)
    assert registry.last_choice("kda") == "pallas_chunked"


def test_head_dim_not_a_lane_multiple_is_refused():
    x = jnp.zeros((64, 1, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="head_dim 64"):
        kda.kda_scan(x, x, x, jnp.zeros((64, 1, 64)), jnp.zeros((64, 1)),
                     ssd.segment_rows(jnp.zeros((64,), jnp.int32), chunk=64))


def test_a_head_in_a_group_is_that_head_alone():
    """Each of eight heads, four a grid step, reads what it reads through
    ``kda_scan`` alone (one head, one a step): the output and the five
    gradients, across a document start inside the first chunk and the
    states each head carries into the next. In and out in float32: a bf16
    result would flip a rounding where the grouped form orders a sum
    otherwise."""
    (q, k, v, g, beta), do, starts = _inputs(**GROUPED)
    q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))
    rows = ssd.segment_rows(jnp.asarray(starts), chunk=kda.CHUNK)
    together = jax.device_get(_scan_vjp(q, k, v, g, beta, do, rows))
    assert registry.last_choice("kda_heads") == "heads4"
    assert registry.last_source("kda_heads") == "shape_rule"
    for h in range(GROUPED["heads"]):
        alone = jax.device_get(_scan_vjp(
            *(x[:, h:h + 1] for x in (q, k, v, g, beta, do)), rows))
        for name, got, ref in zip(NAMES, together, alone):
            assert _rel(got[:, h:h + 1], ref) <= 1e-6, (h, name)


@pytest.mark.parametrize("heads, d, hb", [
    (64, 128, 4), (6, 128, 3), (2, 128, 2), (1, 128, 1), (9, 128, 3),
    # two heads of 256 hold the states of eight of 128
    (64, 256, 1),
])
def test_heads_a_grid_step(heads, d, hb):
    assert kda.heads_per_step(heads, d) == hb
