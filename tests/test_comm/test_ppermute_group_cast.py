"""ppermute group-cast lowering: wire-volume and receive-buffer parity.

VERDICT r1 item 2: the all_to_all lowering pads every (src,dst) pair to the
global max pair, costing ~cp x the honest payload on skewed (causal) masks.
The ppermute lowering pads per ring distance instead (the TPU counterpart of
the reference's true per-pair a2av splits, grpcoll/utils.py:593). Both must
assemble byte-identical receive buffers.
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

from magiattention_tpu.common.enum import AttnMaskType
from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.config import DistAttnConfig, OverlapConfig
from magiattention_tpu.comm.primitives import (
    group_cast_rows,
    group_cast_rows_pp,
    group_reduce_rows,
)
from magiattention_tpu.meta import (
    make_attn_meta_from_dispatch_meta,
    make_dispatch_meta_from_qk_ranges,
)

CP = 8
S = 1024
CHUNK = 32


def make_comm_meta(case="causal", overlap_degree=1, s=S, chunk=CHUNK):
    if case == "causal":
        qr, kr, tm = [[0, s]], [[0, s]], [AttnMaskType.CAUSAL]
    elif case == "sliding_window":
        w = s // 16
        qr = [[0, w], [w, s]]
        kr = [[0, w], [0, s]]
        tm = [AttnMaskType.CAUSAL, AttnMaskType.BICAUSAL]
    else:
        qr, kr, tm = [[0, s]], [[0, s]], [AttnMaskType.FULL]
    config = DistAttnConfig(overlap_config=OverlapConfig(degree=overlap_degree))
    meta_q, meta_kv, bucket = make_dispatch_meta_from_qk_ranges(
        AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr), tm,
        s, s, chunk, CP,
    )
    comm_meta, calc_meta = make_attn_meta_from_dispatch_meta(
        bucket, meta_q, config
    )
    return comm_meta, calc_meta


def test_causal_wire_near_zero_redundant():
    comm_meta, _ = make_comm_meta("causal")
    assert comm_meta.kv_stages, "causal cp=8 must have remote traffic"
    for stage in comm_meta.kv_stages:
        # the planner must pick the cheaper lowering
        assert stage.wire_rows() == min(
            stage.wire_rows("a2a"), stage.wire_rows("ppermute")
        )
    # overall wire volume must be near zero-redundant (VERDICT r1
    # "Done = ratio <= ~1.3 on causal cp=8")
    payload = sum(s.payload_rows() for s in comm_meta.kv_stages)
    wire = sum(s.wire_rows() for s in comm_meta.kv_stages)
    assert payload > 0
    assert wire / payload <= 1.3, f"wire ratio {wire / payload:.2f}"


def test_sliding_window_pp_beats_a2a():
    """Skewed traffic: per-distance padding must beat global-max padding."""
    comm_meta, _ = make_comm_meta("sliding_window", s=4096, chunk=64)
    payload = sum(s.payload_rows() for s in comm_meta.kv_stages)
    wire_pp = sum(s.wire_rows("ppermute") for s in comm_meta.kv_stages)
    wire_a2a = sum(s.wire_rows("a2a") for s in comm_meta.kv_stages)
    assert payload > 0
    assert all(s.lowering == "ppermute" for s in comm_meta.kv_stages)
    assert wire_pp / payload <= 1.3, f"pp wire ratio {wire_pp / payload:.2f}"
    assert wire_pp < 0.65 * wire_a2a, (wire_pp, wire_a2a)


@pytest.mark.parametrize("case", ["causal", "full"])
@pytest.mark.parametrize("overlap_degree", [1, 2])
def test_pp_receive_buffer_matches_a2a(case, overlap_degree):
    comm_meta, calc_meta = make_comm_meta(case, overlap_degree)
    kv_shard = calc_meta.kv_shard_len
    devs = jax.devices()[:CP]
    mesh = Mesh(np.array(devs), ("cp",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((CP * kv_shard, 4)), dtype=jnp.float32
    )

    for stage in comm_meta.kv_stages:
        if stage.pp_send_idx is None:
            continue

        send_idx = jnp.asarray(stage.send_idx)
        recv_sel = jnp.asarray(stage.recv_sel)
        pp_send_idx = jnp.asarray(stage.pp_send_idx)
        pp_recv_sel = jnp.asarray(stage.pp_recv_sel)
        deltas, caps = stage.pp_deltas, stage.pp_caps

        def f(x, si, rs, psi, prs):
            a = group_cast_rows(x, si[0], rs[0], "cp")
            b = group_cast_rows_pp(
                x, psi[0], prs[0], deltas, caps, CP, "cp"
            )
            return a, b

        a, b = shard_map(
            f,
            mesh=mesh,
            in_specs=(P("cp"), P("cp"), P("cp"), P("cp"), P("cp")),
            out_specs=(P("cp"), P("cp")),
            check_vma=False,
        )(x, send_idx, recv_sel, pp_send_idx, pp_recv_sel)

        a = np.asarray(a).reshape(CP, stage.r_max, 4)
        b = np.asarray(b).reshape(CP, stage.r_max, 4)
        for r in range(CP):
            n = int(stage.recv_len[r])
            np.testing.assert_array_equal(
                a[r, :n], b[r, :n],
                err_msg=f"stage receive buffers differ (rank {r})",
            )


def test_ragged_arrays_match_a2a_layout():
    """The ragged_all_to_all tier (TPU-only op) must land segments exactly
    where the solver's receive layout expects them. XLA:CPU lacks the op,
    so validate the planned offsets by simulating its semantics in numpy
    against the a2a path's assembled buffer."""
    from magiattention_tpu.functional.dist_attn import _ragged_arrays

    comm_meta, calc_meta = make_comm_meta("sliding_window", s=2048, chunk=64)
    kv_shard = calc_meta.kv_shard_len
    rng = np.random.default_rng(3)
    x = rng.standard_normal((CP, kv_shard, 4)).astype(np.float32)

    for stage in comm_meta.kv_stages:
        send_row_idx, in_off, send_sz, out_off, recv_sz = (
            np.asarray(a) for a in _ragged_arrays(stage)
        )
        # simulate ragged_all_to_all: src sends its dst-segment of the
        # gathered send buffer; it lands at out_off[src, dst] at the dst
        ragged = np.zeros((CP, stage.r_max, 4), dtype=np.float32)
        for src in range(CP):
            send = x[src][send_row_idx[src]]
            for dst in range(CP):
                n = int(send_sz[src, dst])
                if n:
                    seg = send[in_off[src, dst]: in_off[src, dst] + n]
                    ragged[dst, out_off[src, dst]: out_off[src, dst] + n] = seg
        # a2a reference: dense (cp, a_cap) exchange + recv_sel gather
        for dst in range(CP):
            n = int(stage.recv_len[dst])
            flat = np.zeros((CP * stage.a_cap, 4), dtype=np.float32)
            for src in range(CP):
                c = int(stage.send_counts[src, dst])
                rows = stage.send_idx[src, dst, :c]
                flat[src * stage.a_cap: src * stage.a_cap + c] = x[src][rows]
            expect = flat[stage.recv_sel[dst, :n]]
            np.testing.assert_array_equal(
                ragged[dst, :n], expect,
                err_msg=f"ragged layout mismatch (dst {dst})",
            )


def test_pp_group_reduce_is_transpose():
    """AD through group_cast_rows_pp must equal the explicit a2a reduce."""
    comm_meta, calc_meta = make_comm_meta("causal")
    stage = comm_meta.kv_stages[0]
    if stage.pp_send_idx is None:
        pytest.skip("no pp plan")
    kv_shard = calc_meta.kv_shard_len
    mesh = Mesh(np.array(jax.devices()[:CP]), ("cp",))
    rng = np.random.default_rng(1)
    # partials beyond each rank's recv_len are zero in the runtime (the
    # kernel never writes them); padding rows scatter to different places
    # in the two layouts, so the equivalence only holds with them zeroed
    y_np = rng.standard_normal((CP, stage.r_max, 4))
    for r in range(CP):
        y_np[r, int(stage.recv_len[r]):] = 0.0
    y = jnp.asarray(y_np.reshape(CP * stage.r_max, 4), dtype=jnp.float32)

    send_idx = jnp.asarray(stage.send_idx)
    recv_sel = jnp.asarray(stage.recv_sel)
    pp_send_idx = jnp.asarray(stage.pp_send_idx)
    pp_recv_sel = jnp.asarray(stage.pp_recv_sel)
    deltas, caps = stage.pp_deltas, stage.pp_caps

    def f(y, si, rs, psi, prs):
        a = group_reduce_rows(y, si[0], rs[0], "cp", kv_shard)

        # pp reduce via AD transpose of the pp cast
        def cast(x):
            return group_cast_rows_pp(
                x, psi[0], prs[0], deltas, caps, CP, "cp"
            )

        zeros = jnp.zeros((kv_shard, y.shape[-1]), dtype=y.dtype)
        _, vjp = jax.vjp(cast, zeros)
        (b,) = vjp(y)
        return a, b

    a, b = shard_map(
        f,
        mesh=mesh,
        in_specs=(P("cp"), P("cp"), P("cp"), P("cp"), P("cp")),
        out_specs=(P("cp"), P("cp")),
        check_vma=False,
    )(y, send_idx, recv_sel, pp_send_idx, pp_recv_sel)

    # both reduce exactly the valid rows; summation order differs between
    # the layouts, so allow fp32 rounding noise
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
    )
