"""Property tests for the GroupCast/GroupReduce primitive family.

The reference's comm suite (tests/test_group_collective.py + kernel tests,
~1.6 kLoC) hammers group_cast/group_reduce with randomized dst/src sets and
checks the reduce against a dense scatter-sum oracle. TPU equivalent, on the
8-device CPU mesh:

- random multicast patterns: cast receive buffers match a numpy oracle;
- group_reduce is the EXACT linear transpose of group_cast (dot-product
  identity <cast(x), y> == <x, reduce(y)>) for both the a2a and ppermute
  tiers — this is what makes the CP backward exact, so it is pinned as a
  property over random patterns, not a single example;
- jax.grad through a cast matches the hand-built reduce (AD transpose);
- degenerate patterns: empty sends, self-only, single-row shards.
"""

import pytest

# heavy property/e2e suites: the slow tier (make test-all); the fast
# tier keeps this area covered via its smaller sibling files
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from magiattention_tpu.comm.primitives import (
    group_cast_rows,
    group_cast_rows_pp,
    group_reduce_rows,
    group_reduce_rows_pp,
)

CP = 4
SHARD = 16
FEAT = 3


def mesh4():
    return Mesh(np.array(jax.devices("cpu")[:CP]), ("cp",))


def random_pattern(seed: int):
    """Random multicast: for each (dst, src) pair an arbitrary subset of
    src's rows (possibly empty; rows may go to several dsts). Returns
    per-rank (send_idx (cp, A), recv_sel (R,)) in the a2a layout plus the
    dense numpy oracle of every rank's receive buffer."""
    rng = np.random.default_rng(seed)
    want = [
        [
            np.sort(
                rng.choice(
                    SHARD,
                    size=int(rng.integers(0, SHARD // 2 + 1)),
                    replace=False,
                )
            )
            for _src in range(CP)
        ]
        for _dst in range(CP)
    ]
    a_cap = max(
        (len(want[d][s]) for d in range(CP) for s in range(CP)), default=1
    )
    a_cap = max(a_cap, 1)
    send_idx = np.zeros((CP, CP, a_cap), np.int32)  # [src, dst, A]
    for s in range(CP):
        for d in range(CP):
            rows = want[d][s]
            send_idx[s, d, : len(rows)] = rows
    recv_sel = []  # [dst] -> flat src*A+pos selectors
    for d in range(CP):
        sel = []
        for s in range(CP):
            sel.extend(s * a_cap + p for p in range(len(want[d][s])))
        recv_sel.append(np.asarray(sel, np.int32))
    return want, send_idx, recv_sel, a_cap


def run_cast(x_all, send_idx, recv_sel_padded, n_recv):
    """shard_map'd a2a-tier cast; recv buffers padded to a common R cap."""

    def f(x, si, rs):
        return group_cast_rows(x[0], si[0], rs[0], "cp")[None]

    y = shard_map(
        f,
        mesh=mesh4(),
        in_specs=(P("cp"), P("cp"), P("cp")),
        out_specs=P("cp"),
        check_vma=False,
    )(x_all, send_idx, recv_sel_padded)
    return [np.asarray(y[r, :n]) for r, n in enumerate(n_recv)]


@pytest.mark.parametrize("seed", range(8))
def test_cast_matches_oracle(seed):
    want, send_idx, recv_sel, a_cap = random_pattern(seed)
    rng = np.random.default_rng(100 + seed)
    x = rng.standard_normal((CP, SHARD, FEAT)).astype(np.float32)
    n_recv = [len(s) for s in recv_sel]
    r_cap = max(max(n_recv), 1)
    rs_pad = np.zeros((CP, r_cap), np.int32)
    for d in range(CP):
        rs_pad[d, : n_recv[d]] = recv_sel[d]
    got = run_cast(
        jnp.asarray(x), jnp.asarray(send_idx), jnp.asarray(rs_pad), n_recv
    )
    for d in range(CP):
        expect = (
            np.concatenate([x[s][want[d][s]] for s in range(CP)])
            if n_recv[d]
            else np.zeros((0, FEAT), np.float32)
        )
        np.testing.assert_array_equal(got[d], expect, err_msg=f"dst {d}")


@pytest.mark.parametrize("seed", range(8))
def test_reduce_is_exact_transpose(seed):
    """<cast(x), y> == <x, reduce(y)> summed over ranks — the linear-
    transpose identity that makes the CP backward exact."""
    want, send_idx, recv_sel, a_cap = random_pattern(seed)
    rng = np.random.default_rng(200 + seed)
    x = rng.standard_normal((CP, SHARD, FEAT)).astype(np.float32)
    n_recv = [len(s) for s in recv_sel]
    r_cap = max(max(n_recv), 1)
    rs_pad = np.zeros((CP, r_cap), np.int32)
    y = np.zeros((CP, r_cap, FEAT), np.float32)
    for d in range(CP):
        rs_pad[d, : n_recv[d]] = recv_sel[d]
        y[d, : n_recv[d]] = rng.standard_normal((n_recv[d], FEAT))

    cast_out = run_cast(
        jnp.asarray(x), jnp.asarray(send_idx), jnp.asarray(rs_pad), n_recv
    )

    def g(yv, si, rs):
        return group_reduce_rows(yv[0], si[0], rs[0], "cp", SHARD)[None]

    red = shard_map(
        g,
        mesh=mesh4(),
        in_specs=(P("cp"), P("cp"), P("cp")),
        out_specs=P("cp"),
        check_vma=False,
    )(jnp.asarray(y), jnp.asarray(send_idx),
      jnp.asarray(rs_pad))
    red = np.asarray(red)

    lhs = sum(
        float((cast_out[d] * y[d, : n_recv[d]]).sum()) for d in range(CP)
    )
    rhs = float((x * red).sum())
    # padding positions (send_idx pad=0, y pad=0) contribute exactly 0
    assert abs(lhs - rhs) < 1e-3 * max(1.0, abs(lhs)), (seed, lhs, rhs)


@pytest.mark.parametrize("seed", range(4))
def test_grad_through_cast_matches_reduce(seed):
    """jax.grad of sum(cast(x) * y) must equal the hand-built
    group_reduce of y — AD's transpose and ours agree row-for-row."""
    want, send_idx, recv_sel, a_cap = random_pattern(seed)
    rng = np.random.default_rng(300 + seed)
    x = rng.standard_normal((CP, SHARD, FEAT)).astype(np.float32)
    n_recv = [len(s) for s in recv_sel]
    r_cap = max(max(n_recv), 1)
    rs_pad = np.zeros((CP, r_cap), np.int32)
    yw = np.zeros((CP, r_cap, FEAT), np.float32)
    for d in range(CP):
        rs_pad[d, : n_recv[d]] = recv_sel[d]
        yw[d, : n_recv[d]] = rng.standard_normal((n_recv[d], FEAT))
    mask = np.zeros((CP, r_cap, 1), np.float32)
    for d in range(CP):
        mask[d, : n_recv[d]] = 1.0

    si = jnp.asarray(send_idx)
    rs = jnp.asarray(rs_pad)
    yj = jnp.asarray(yw * mask)

    def loss_fn(xv):
        def f(x, si_, rs_, y_):
            c = group_cast_rows(x[0], si_[0], rs_[0], "cp")
            return jnp.sum(c * y_[0])[None]

        per = shard_map(
            f,
            mesh=mesh4(),
            in_specs=(P("cp"), P("cp"), P("cp"), P("cp")),
            out_specs=P("cp"),
            check_vma=False,
        )(xv, si, rs, yj)
        return jnp.sum(per)

    gx = np.asarray(jax.grad(loss_fn)(jnp.asarray(x)))

    def g(yv, si_, rs_):
        return group_reduce_rows(yv[0], si_[0], rs_[0], "cp", SHARD)[None]

    red = np.asarray(
        shard_map(
            g,
            mesh=mesh4(),
            in_specs=(P("cp"), P("cp"), P("cp")),
            out_specs=P("cp"),
            check_vma=False,
        )(yj, si, rs)
    )
    np.testing.assert_allclose(gx, red, rtol=1e-5, atol=1e-5)


def _pp_layout(want, cp):
    """Build the ppermute-tier layout (send_idx, recv_sel, deltas, caps)
    from a dst<-src want table, mirroring the solver's pp lowering."""
    deltas = []
    caps = []
    for delta in range(1, cp):
        pair_sizes = [len(want[(s + delta) % cp][s]) for s in range(cp)]
        if any(pair_sizes):
            deltas.append(delta)
            caps.append(max(pair_sizes))
    send_idx, recv_sel = [], []
    for r in range(cp):
        si = []
        for delta, c in zip(deltas, caps):
            rows = want[(r + delta) % cp][r]
            si.extend(rows.tolist() + [0] * (c - len(rows)))
        send_idx.append(np.asarray(si, np.int32))
        sel = []
        off = 0
        for delta, c in zip(deltas, caps):
            src = (r - delta) % cp
            rows = want[r][src]
            sel.extend(range(off, off + len(rows)))
            off += c
        recv_sel.append(np.asarray(sel, np.int32))
    return send_idx, recv_sel, tuple(deltas), tuple(caps)


@pytest.mark.parametrize("seed", range(6))
def test_pp_tier_transpose_identity(seed):
    """The ppermute tier satisfies the same dot-product transpose identity
    (its reduce rides AD-transposed inverse rings)."""
    want, _, _, _ = random_pattern(seed)
    for d in range(CP):  # pp tier carries no self-rows
        want[d][d] = np.zeros((0,), np.int64)
    send_idx, recv_sel, deltas, caps = _pp_layout(want, CP)
    if not deltas:
        pytest.skip("empty pattern")
    rng = np.random.default_rng(400 + seed)
    x = rng.standard_normal((CP, SHARD, FEAT)).astype(np.float32)
    n_recv = [len(s) for s in recv_sel]
    r_cap = max(max(n_recv), 1)
    si_pad = np.stack(send_idx)
    rs_pad = np.zeros((CP, r_cap), np.int32)
    y = np.zeros((CP, r_cap, FEAT), np.float32)
    for r in range(CP):
        rs_pad[r, : n_recv[r]] = recv_sel[r]
        y[r, : n_recv[r]] = rng.standard_normal((n_recv[r], FEAT))

    def f(x, si_, rs_):
        return group_cast_rows_pp(
            x[0], si_[0], rs_[0], deltas, caps, CP, "cp"
        )[None]

    cast = np.asarray(
        shard_map(
            f,
            mesh=mesh4(),
            in_specs=(P("cp"), P("cp"), P("cp")),
            out_specs=P("cp"),
            check_vma=False,
        )(jnp.asarray(x), jnp.asarray(si_pad),
          jnp.asarray(rs_pad))
    )
    # oracle check of the cast itself
    for r in range(CP):
        expect_rows = [
            x[(r - delta) % CP][want[r][(r - delta) % CP]]
            for delta in deltas
        ]
        expect = (
            np.concatenate(expect_rows)
            if n_recv[r]
            else np.zeros((0, FEAT), np.float32)
        )
        np.testing.assert_array_equal(
            cast[r, : n_recv[r]], expect, err_msg=f"pp cast rank {r}"
        )

    def g(yv, si_, rs_):
        return group_reduce_rows_pp(
            yv[0], si_[0], rs_[0], deltas, caps, CP, "cp", SHARD
        )[None]

    red = np.asarray(
        shard_map(
            g,
            mesh=mesh4(),
            in_specs=(P("cp"), P("cp"), P("cp")),
            out_specs=P("cp"),
            check_vma=False,
        )(jnp.asarray(y), jnp.asarray(si_pad),
          jnp.asarray(rs_pad))
    )
    lhs = sum(float((cast[r, : n_recv[r]] * y[r, : n_recv[r]]).sum())
              for r in range(CP))
    rhs = float((x * red).sum())
    assert abs(lhs - rhs) < 1e-3 * max(1.0, abs(lhs)), (seed, lhs, rhs)


def test_empty_pattern_cast_reduce():
    """All-empty sends: cast returns padding only, reduce returns zeros."""
    send_idx = np.zeros((CP, CP, 1), np.int32)
    rs_pad = np.zeros((CP, 1), np.int32)
    x = np.ones((CP, SHARD, FEAT), np.float32)
    y = np.zeros((CP, 1, FEAT), np.float32)

    def g(yv, si_, rs_):
        return group_reduce_rows(yv[0], si_[0], rs_[0], "cp", SHARD)[None]

    red = np.asarray(
        shard_map(
            g,
            mesh=mesh4(),
            in_specs=(P("cp"), P("cp"), P("cp")),
            out_specs=P("cp"),
            check_vma=False,
        )(jnp.asarray(y), jnp.asarray(send_idx),
          jnp.asarray(rs_pad))
    )
    np.testing.assert_array_equal(red, np.zeros_like(red))
