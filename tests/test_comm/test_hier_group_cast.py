"""Hierarchical (DCN x ICI) group-cast tests.

Ref: tests/test_comm/test_group_collective.py (hier impl rows) — the 2-phase
hierarchical cast must produce byte-identical receive buffers to the flat
1-phase cast, while strictly deduplicating inter-node traffic for multicast
patterns.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from magiattention_tpu.common.range import AttnRange
from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.comm.hier import (
    hier_group_cast_rows,
    make_hier_group_cast_plan,
)
from magiattention_tpu.comm.primitives import group_cast_rows
from magiattention_tpu.meta.solver.dynamic_attn_solver import _make_cast_arg

N_OUTER, N_INNER = 2, 4
CP = N_OUTER * N_INNER
SHARD = 32
ALIGN = 8  # small alignment for test readability


def _host_ranges():
    return [
        AttnRanges([AttnRange(r * SHARD, (r + 1) * SHARD)]) for r in range(CP)
    ]


def _random_requests(seed, multicast=True):
    """Random (dst, src) requests; multicast=True repeats the same src rows
    to several dsts in one node (the case hier comm deduplicates)."""
    rng = np.random.default_rng(seed)
    reqs = [[AttnRanges() for _ in range(CP)] for _ in range(CP)]
    for dst in range(CP):
        for src in range(CP):
            if src == dst:
                continue
            if multicast and src % 2 == 0:
                # same rows requested by every rank of dst's node
                s0 = src * SHARD + 4
                reqs[dst][src].append(AttnRange(s0, s0 + 12))
            elif rng.random() < 0.5:
                a = int(rng.integers(0, SHARD - 8))
                ln = int(rng.integers(1, 8))
                reqs[dst][src].append(
                    AttnRange(src * SHARD + a, src * SHARD + a + ln)
                )
    for dst in range(CP):
        for src in range(CP):
            reqs[dst][src] = reqs[dst][src].merge()
    return reqs


@pytest.mark.parametrize("multicast", [True, False])
@pytest.mark.parametrize(
    "seed", [0, pytest.param(1, marks=pytest.mark.slow)]
)
def test_hier_matches_flat(seed, multicast):
    reqs = _random_requests(seed, multicast)
    host = _host_ranges()

    flat = _make_cast_arg(reqs, host, CP, ALIGN, r_max=None or 512)
    plan = make_hier_group_cast_plan(
        reqs, host, N_OUTER, N_INNER, alignment=ALIGN, r_max=512
    )

    devs = np.array(jax.devices("cpu")[:CP]).reshape(N_OUTER, N_INNER)
    mesh = Mesh(devs, axis_names=("dcn", "ici"))

    rng = np.random.default_rng(100 + seed)
    x = jnp.asarray(rng.standard_normal((CP * SHARD, 4)), dtype=jnp.float32)

    spec2 = P(("dcn", "ici"))

    def flat_f(x, send_idx, recv_sel):
        return group_cast_rows(x, send_idx[0], recv_sel[0], ("dcn", "ici"))

    flat_out = shard_map(
        flat_f, mesh=mesh,
        in_specs=(spec2, spec2, spec2), out_specs=spec2,
        check_vma=False,
    )(x, jnp.asarray(flat.send_idx), jnp.asarray(flat.recv_sel))

    def hier_f(x, a_s, a_r, b_s, b_r):
        return hier_group_cast_rows(
            x, a_s[0][0], a_r[0][0], b_s[0][0], b_r[0][0], "dcn", "ici"
        )

    spec_a = P("dcn", "ici")
    hier_out = shard_map(
        hier_f, mesh=mesh,
        in_specs=(spec2, spec_a, spec_a, spec_a, spec_a),
        out_specs=spec2,
        check_vma=False,
    )(
        x,
        jnp.asarray(plan.a_send_idx.reshape(N_OUTER, N_INNER, *plan.a_send_idx.shape[1:])),
        jnp.asarray(plan.a_recv_sel.reshape(N_OUTER, N_INNER, -1)),
        jnp.asarray(plan.b_send_idx.reshape(N_OUTER, N_INNER, *plan.b_send_idx.shape[1:])),
        jnp.asarray(plan.b_recv_sel.reshape(N_OUTER, N_INNER, -1)),
    )

    # compare valid rows per rank (beyond recv_len both are padding)
    flat_np = np.asarray(flat_out).reshape(CP, -1, 4)
    hier_np = np.asarray(hier_out).reshape(CP, -1, 4)
    for r in range(CP):
        n = int(flat.recv_len[r])
        np.testing.assert_allclose(
            hier_np[r, :n], flat_np[r, :n], err_msg=f"rank {r}"
        )


def _geom_host_ranges(cp):
    return [
        AttnRanges([AttnRange(r * SHARD, (r + 1) * SHARD)]) for r in range(cp)
    ]


def _check_against_flat(plan, reqs, host, cp):
    """Numpy-simulate phase A + phase B and require byte-identity with the
    flat cast (the verifier's R3 fabric-split sub-check)."""
    from magiattention_tpu.analysis.verifier import check_hier_plan
    from magiattention_tpu.analysis.violation import VerifyReport

    flat = _make_cast_arg(reqs, host, cp, ALIGN, r_max=512)
    report = VerifyReport()
    check_hier_plan(report, plan, flat, host, "edge")
    assert not report.errors(), [str(v) for v in report.errors()]


def test_hier_single_node_no_dcn(tmp_path, monkeypatch):
    """n_outer=1: the dcn axis is degenerate — zero rows may cross it and
    the telemetry dedup ratio must be exactly 1.0."""
    import json

    from magiattention_tpu import telemetry

    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path))
    telemetry.reset()
    try:
        reqs = _random_requests(3, multicast=True)
        host = _host_ranges()
        plan = make_hier_group_cast_plan(
            reqs, host, 1, CP, alignment=ALIGN, r_max=512
        )
        assert plan.n_outer == 1 and plan.n_inner == CP
        assert plan.dcn_rows() == 0
        assert int(np.asarray(plan.a_recv_len).sum()) == 0
        _check_against_flat(plan, reqs, host, CP)
    finally:
        telemetry.reset()  # flush + close the JSONL handle in tmp_path
    records = []
    for fp in sorted(tmp_path.glob("*.jsonl")):
        with open(fp) as f:
            records += [json.loads(ln) for ln in f if ln.strip()]
    hier = [r for r in records if r.get("kind") == "hier_plan"]
    assert hier and hier[-1]["dcn_dedup_ratio"] == 1.0


def test_hier_single_rank_inner():
    """n_inner=1: every rank is its own node — phase B degenerates to a
    local copy and every cross-rank row crosses the DCN exactly once."""
    reqs = _random_requests(4, multicast=False)
    host = _host_ranges()
    plan = make_hier_group_cast_plan(
        reqs, host, CP, 1, alignment=ALIGN, r_max=512
    )
    # with one rank per node there is no intra-node multicast to dedup:
    # DCN rows == all cross-rank request rows
    assert plan.dcn_rows() == sum(
        reqs[d][s].total_seqlen
        for d in range(CP)
        for s in range(CP)
        if d != s
    )
    _check_against_flat(plan, reqs, host, CP)


def test_hier_ragged_all_to_one():
    """Ragged all-to-one: every rank requests the same rows of rank 0's
    shard (plus ragged per-rank extras). The shared rows must cross the
    DCN once per *remote node*, not once per requesting rank."""
    shared = AttnRange(4, 4 + 20)
    reqs = [[AttnRanges() for _ in range(CP)] for _ in range(CP)]
    for dst in range(1, CP):
        reqs[dst][0].append(shared)
        # ragged tail: each dst also wants a distinct extra row count
        reqs[dst][0].append(AttnRange(24, 24 + dst % 3))
        reqs[dst][0] = reqs[dst][0].merge()
    host = _host_ranges()
    plan = make_hier_group_cast_plan(
        reqs, host, N_OUTER, N_INNER, alignment=ALIGN, r_max=512
    )
    # exactly-once per remote node: the node-level union of requests from
    # src 0, summed over nodes that don't own src 0
    expect = sum(
        AttnRanges(
            [g for d in range(CP) if d // N_INNER == o for g in reqs[d][0]]
        ).merge().total_seqlen
        for o in range(1, N_OUTER)
    )
    assert plan.dcn_rows() == expect
    _check_against_flat(plan, reqs, host, CP)


def test_hier_dedups_dcn_traffic():
    reqs = _random_requests(0, multicast=True)
    host = _host_ranges()
    plan = make_hier_group_cast_plan(
        reqs, host, N_OUTER, N_INNER, alignment=ALIGN
    )
    # flat DCN rows: every cross-node (dst, src) request row crosses DCN
    flat_dcn = sum(
        reqs[d][s].total_seqlen
        for d in range(CP)
        for s in range(CP)
        if d // N_INNER != s // N_INNER
    )
    assert plan.dcn_rows() < flat_dcn  # multicast rows crossed once, not 4x
    # lower bound: each (dst_node, src, row) crosses exactly once
    assert plan.dcn_rows() == sum(
        AttnRanges(
            [g for d in range(CP) if d // N_INNER == o for g in reqs[d][s]]
        ).merge().total_seqlen
        for o in range(N_OUTER)
        for s in range(CP)
        if s // N_INNER != o
    )
