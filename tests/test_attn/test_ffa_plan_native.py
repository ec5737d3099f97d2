"""Native (C) FFA plan builder vs the pure-Python builder: bit-exact parity.

The native builder (csrc/magi_host.cpp magi_ffa_plan_{count,fill}) is the
host-side analogue of the reference's native tile schedulers
(csrc/flexible_flash_attention/fwd_tile_scheduler.hpp); it is the default
(MAGI_ATTENTION_NATIVE_FFA_PLAN=auto) and must agree with the Python
builder on every array, including dummy items for empty tiles and
is_first/is_last run flags.
"""

import numpy as np
import pytest

from magiattention_tpu.kernels import ffa_plan as fp

pytest.importorskip("magiattention_tpu.csrc_backend.ops")


def _build(monkeypatch, mode, *args):
    monkeypatch.setenv("MAGI_ATTENTION_NATIVE_FFA_PLAN", mode)
    return fp.build_ffa_plan(*args)


def _assert_same(a, b):
    for name in ("work_qt", "work_kt", "meta", "work_qt_t", "work_kt_t",
                 "meta_t"):
        x, y = getattr(a, name), getattr(b, name)
        assert x.shape == y.shape, name
        assert (x == y).all(), name


@pytest.mark.parametrize("seed", range(40))
def test_native_plan_parity_random(seed, monkeypatch):
    try:
        from magiattention_tpu.csrc_backend.build import get_lib

        get_lib()
    except ImportError:
        pytest.skip("native lib unavailable")
    rng = np.random.default_rng(seed)
    sq = int(rng.integers(64, 2048))
    sk = int(rng.integers(64, 2048))
    bq = int(rng.choice([64, 128, 256]))
    bk = int(rng.choice([128, 256, 512]))
    n = int(rng.integers(1, 12))
    qr = np.sort(rng.integers(0, sq, (n, 2)), axis=1).astype(np.int32)
    kr = np.sort(rng.integers(0, sk, (n, 2)), axis=1).astype(np.int32)
    lo = rng.integers(-sk, sk // 2, n).astype(np.int32)
    hi = (lo + rng.integers(-3, sk, n)).astype(np.int32)
    args = (qr, kr, lo, hi, sq, sk, bq, bk)
    _assert_same(_build(monkeypatch, "1", *args),
                 _build(monkeypatch, "0", *args))


def test_native_plan_parity_band_inf(monkeypatch):
    """Unbounded bands + empty tiles (the dummy-item path)."""
    try:
        from magiattention_tpu.csrc_backend.build import get_lib

        get_lib()
    except ImportError:
        pytest.skip("native lib unavailable")
    from magiattention_tpu.kernels.mask_utils import BAND_INF

    qr = np.array([[0, 100], [300, 400]], np.int32)
    kr = np.array([[0, 100], [0, 50]], np.int32)
    lo = np.array([-BAND_INF, -BAND_INF], np.int32)
    hi = np.array([0, BAND_INF], np.int32)
    args = (qr, kr, lo, hi, 512, 512, 128, 128)
    a = _build(monkeypatch, "1", *args)
    b = _build(monkeypatch, "0", *args)
    _assert_same(a, b)
    # rows 100-300 and 400-512 are uncovered: q tiles 1 and 3 get dummies
    assert a.num_q_tiles == 4


def test_native_plan_rejects_out_of_grid(monkeypatch):
    """Ranges beyond the tile grid must raise, never corrupt buffers."""
    try:
        from magiattention_tpu.csrc_backend.build import get_lib

        get_lib()
    except ImportError:
        pytest.skip("native lib unavailable")
    qr = np.array([[0, 700]], np.int32)  # beyond seqlen_q=512
    kr = np.array([[0, 128]], np.int32)
    lo = np.array([-1 << 30], np.int32)
    hi = np.array([1 << 30], np.int32)
    with pytest.raises((ValueError, IndexError)):
        _build(monkeypatch, "1", qr, kr, lo, hi, 512, 512, 128, 128)


@pytest.mark.parametrize("mode", ["0", "1"])
@pytest.mark.parametrize(
    "qr_row,kr_row",
    [((-64, 128), (0, 128)), ((0, 128), (-64, 128)), ((0, 700), (0, 128))],
)
def test_plan_builders_reject_bad_ranges_identically(
    monkeypatch, mode, qr_row, kr_row
):
    """Both builders raise ValueError on negative/out-of-grid starts; the
    Python fallback must not silently wrap via negative indexing (ADVICE r2)."""
    if mode == "1":
        try:
            from magiattention_tpu.csrc_backend.build import get_lib

            get_lib()
        except ImportError:
            pytest.skip("native lib unavailable")
    qr = np.array([qr_row], np.int32)
    kr = np.array([kr_row], np.int32)
    lo = np.array([-1 << 30], np.int32)
    hi = np.array([1 << 30], np.int32)
    with pytest.raises(ValueError):
        _build(monkeypatch, mode, qr, kr, lo, hi, 512, 512, 128, 128)


# -- the k-major list's revisit distance --------------------------------------


@pytest.mark.parametrize("work_qt,want", [
    ([5, 5, 5, 5], fp.NO_REVISIT),           # one adjacent run: resident
    ([0, 1, 2, 3], fp.NO_REVISIT),           # no q tile twice
    ([0, 0, 1, 1, 2, 2], fp.NO_REVISIT),     # distance 1 only: adjacent
    ([0, 1, 0], 2),                          # left for one step
    ([0, 0, 1, 0, 0], 2),                    # counted from the run's END
    ([0, 1, 2, 0], 3),
    ([0, 1, 2, 3, 4, 5, 6, 0], 7),           # many
    ([0, 1, 2, 3, 0, 2, 1], 3),              # the least of several
    ([0, 1, 2, 0, 1, 2, 2, 2], 3),           # pad_plan's filler: the last
                                             # tile again, only a longer run
    ([], fp.NO_REVISIT),
    ([3], fp.NO_REVISIT),
])
def test_min_revisit_distance_on_hand_built_lists(work_qt, want):
    assert fp.min_revisit_distance(np.asarray(work_qt, np.int32)) == want


def _causal_docs_plan(lens, window=None, bq=256, bk=512):
    cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    qr = np.stack([cu[:-1], cu[1:]], axis=1)
    lo = np.full(len(lens), -(window - 1) if window else -(1 << 30), np.int32)
    hi = np.zeros(len(lens), np.int32)
    return fp.build_ffa_plan(qr, qr.copy(), lo, hi, int(cu[-1]), int(cu[-1]),
                             bq, bk)


def _cell_mask(cell_name):
    """The cell's own mask at its own size, from its traffic file."""
    from cellbench import manifest, run, traffic_gen

    cell = manifest.load_cell(manifest.ROOT, cell_name)
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    _, tokens, window, _ = run.cell_sizes(cell, family, 0)
    spec = traffic_gen.make_mask(
        cell.traffic, tokens, window, 0,
        manifest.load_generator(manifest.ROOT, cell.traffic["generator"]))
    return family, spec


# W of both lists as PERF.md §4 has them
CELL_W = {
    "nemo12b.longdoc.cp1": 1056,
    "nemo12b.packed.cp1": 315,
    "mistral7b.swa32k.cp1": 1096,
}


@pytest.mark.parametrize("cell", sorted(CELL_W))
def test_cells_plans_leave_a_q_tile_for_four_steps(cell, monkeypatch):
    from magiattention_tpu.kernels.mask_utils import types_to_bands

    family, spec = _cell_mask(cell)
    qr, kr, types = family.mask_slices(spec)
    qr = np.asarray(qr.to_naive_ranges(), np.int32)
    kr = np.asarray(kr.to_naive_ranges(), np.int32)
    lo, hi = types_to_bands(
        qr, kr, np.asarray([t.to_int_type() for t in types], np.int32))
    args = (qr, kr, lo, hi, spec.tokens, spec.tokens, 256, 512)
    plan = fp.build_ffa_plan(*args)
    assert (plan.num_work, plan.num_work_t) == (CELL_W[cell], CELL_W[cell])
    assert plan.min_revisit_distance == 4
    # walked upwards, as before PR 30, the same lists come back after 2
    monkeypatch.setattr(fp, "_late_revisit_order", lambda *a: a)
    assert fp.build_ffa_plan(*args).min_revisit_distance == 2


def test_the_four_chip_cells_ranks_share_the_least_distance():
    """``nemo12b.longdoc.cp4``: the dispatch solver's own chunks on four
    virtual devices, the merged plan of each rank stacked — the params
    carry the least distance over the ranks: 3, where a rank's last own
    chunk is a run of two q tiles between two runs of four over them
    (walked down every time, as on one chip, it would read 2), which is
    what the one-pass backward needs."""
    import jax
    from jax.sharding import Mesh

    from magiattention_tpu.kernels.ffa import FUSED_DQ_REVISIT_DISTANCE

    family, spec = _cell_mask("nemo12b.longdoc.cp4")
    tokens = spec.tokens
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("cp",))
    key, _ = family.timed_plan(spec, mesh)
    rt = family._mgr(key).runtime
    _, _, w, wt, fields = rt._merged_dims
    assert (w, wt) == (1040, 1043)
    per_rank = [
        fp.build_ffa_plan(a.q_ranges, a.k_ranges, a.d_lo, a.d_hi,
                          tokens // 4, tokens, 256, 512).min_revisit_distance
        for a in rt.calc_meta.merged_args]
    assert per_rank == [3, 3, 3, 4]
    assert fields["min_revisit_distance"] == min(per_rank)
    assert min(per_rank) == FUSED_DQ_REVISIT_DISTANCE


def _ordered(qt, kt):
    """``_late_revisit_order`` of a hand-built k-major list -> its q tiles."""
    qt, kt = np.asarray(qt, np.int32), np.asarray(kt, np.int32)
    meta = np.zeros((len(qt), 9), np.int32)
    meta[:, fp.QS] = np.arange(len(qt))  # tells the items apart
    out_qt, out_kt, out_meta = fp._late_revisit_order(qt, kt, meta)
    assert (out_kt == kt).all()
    # the same items: a row's q tile travels with it
    assert (qt[out_meta[:, fp.QS]] == out_qt).all()
    assert sorted(out_meta[:, fp.QS]) == list(range(len(qt)))
    return out_qt.tolist()


@pytest.mark.parametrize("qt, kt, want, dist", [
    # a causal document's tail: every run from its last q tile down
    ([4, 5, 6, 7, 6, 7], [0, 0, 0, 0, 1, 1], [7, 6, 5, 4, 7, 6], 4),
    # a short run between two longer ones over its q tiles (a chunked
    # rank at cp 4): the third run walked up reads 3, walked down 2
    ([0, 1, 2, 3, 2, 3, 0, 1, 2, 3], [0, 0, 0, 0, 1, 1, 2, 2, 2, 2],
     [3, 2, 1, 0, 3, 2, 0, 1, 2, 3], 3),
    # the same two q tiles under two k tiles in a row: the second run
    # starts on the tile the walk stands on, the other comes back at 3
    ([0, 1, 0, 1], [0, 0, 1, 1], [1, 0, 0, 1], 3),
    # two slices' items on one (q, k) pair become neighbours
    ([2, 3, 2, 3, 4], [0, 0, 0, 0, 0], [4, 3, 3, 2, 2], fp.NO_REVISIT),
])
def test_late_revisit_order_on_hand_built_lists(qt, kt, want, dist):
    got = _ordered(qt, kt)
    assert got == want
    assert fp.min_revisit_distance(got) == dist


def test_late_revisit_order_keeps_the_runs_and_their_flags():
    """Only the order inside a k tile's run changes: same items, same
    runs, IS_FIRST on each run's first row and IS_LAST on its last."""
    plan = _causal_docs_plan([700, 1348], bq=128, bk=256)
    kt, qt, meta = plan.work_kt_t, plan.work_qt_t, plan.meta_t
    assert (np.diff(kt) >= 0).all()  # still k-major
    starts = np.flatnonzero(np.diff(kt, prepend=-1))
    ends = np.append(starts[1:], len(kt)) - 1
    assert (meta[starts, fp.IS_FIRST] == 1).all()
    assert (meta[ends, fp.IS_LAST] == 1).all()
    assert meta[:, fp.IS_FIRST].sum() == meta[:, fp.IS_LAST].sum() == len(
        starts)
    for s, e in zip(starts, ends):
        # one direction a run: from the last q tile down, or up
        assert (np.diff(qt[s:e + 1]) <= 0).all() or (
            np.diff(qt[s:e + 1]) >= 0).all()
    # the q-major list is untouched: q tiles ascend
    assert (np.diff(plan.work_qt) >= 0).all()
