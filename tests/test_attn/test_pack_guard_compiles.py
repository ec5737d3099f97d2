"""What ``ffa.gqa_pack_fits`` admits, the v5e compiler takes — and what the
compiler refused, the guard keeps out.

The packed q-major bodies (fwd, dq) are on by default wherever there is a
GQA group (PR 25), so ``gqa_pack_fits`` is all that stands between a
default call and a Mosaic VMEM refusal. Its two q-major bounds
(``Q_MAJOR_PACK_MAX_ROWS``, ``Q_MAJOR_PACK_MAX_BYTES``) were fitted to a
compile sweep; this file is that sweep: g x head dims x dtype at the
256 x 512 tile, forward and backward, compiled for one chip of a
described ``v5e:2x2`` from the CPU, as
``tests/test_cellbench/test_cells_lower_for_tpu.py`` does for the cells'
shapes (VMEM is checked there and costs no chip time). Skipped where no
such topology can be described.

Since PR 36 ``block_q`` follows the group (``tile_policy.group_block_q``:
128 rows at g = 8, 64 at g = 16, where every body packs and the plan's
table fits), so the sweep has a second half at the tiles the rule moves to,
split and one-pass backward, and the table's capacity
(``ffa.PLAN_TABLE_MAX_WORK``) is compiled at and refused just beyond.
"""

import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

S = 1024  # four q tiles, two k tiles: the default tiles unclamped
DTYPES = ("bfloat16", "float32")
HEAD_DIMS = ((64, 64), (128, 128), (192, 128), (256, 256))
GROUPS = (2, 4, 8)
# packed dkv at 2048 rows: the loose guard it had before PR 25 admits
# these two and the compiler refuses them (18.44 and 16.13 of 16 MiB for a
# model of 13.03 and 11.28) — the parent's default too, PERF.md section 7
DKV_REFUSED = {("bfloat16", 192, 128, 8), ("float32", 64, 64, 8)}
# (dtype, d, dv, g, pass): what the compiler refused of the packed q-major
# bodies under the plain VMEM budget they had before PR 25
Q_MAJOR_REFUSED = [
    ("bfloat16", 64, 64, 8, "fwd"), ("bfloat16", 64, 64, 8, "dq"),
    ("bfloat16", 128, 128, 8, "fwd"), ("bfloat16", 128, 128, 8, "dq"),
    ("bfloat16", 192, 128, 8, "fwd"), ("bfloat16", 256, 256, 4, "dq"),
    ("float32", 64, 64, 8, "dq"),
]
# the tile the group's rule moves block_q to, where it moves it
RULE_TILES = {8: 128, 16: 64}
# (dtype, d, dv, g, pass) at RULE_TILES: what the compiler refuses of the
# packed q-major bodies there under the plain VMEM budget — the packed dq
# at d = 256 in bf16, as at g = 4 x 256 rows (float32 compiles: the guard
# is the tighter one there); the rule keeps the default tile. The same
# step at g = 16 x 64 rows is refused alike (compile sweep, PR 36) and
# cannot be a case: beside a packed dq the plain dkv body does not lower
# at 64 rows (its lse block is 64 lanes).
Q_MAJOR_REFUSED_AT_RULE_TILES = [("bfloat16", 256, 256, 8, "dq")]
FLAGS = {"fwd": "MAGI_ATTENTION_FFA_GQA_PACK",
         "dq": "MAGI_ATTENTION_FFA_GQA_PACK_DQ",
         "dkv": "MAGI_ATTENTION_FFA_GQA_PACK_DKV"}


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """The real (not interpreted) kernel path, split backward, no tile or
    pack key from the environment."""
    import magiattention_tpu.api  # noqa: F401  (binds _should_interpret)
    from magiattention_tpu.kernels import ffa

    monkeypatch.setattr(ffa, "_should_interpret", lambda: False)
    for key in list(os.environ):
        if key.startswith("MAGI_ATTENTION_FFA_"):
            monkeypatch.delenv(key)
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_FFA_BWD", "split")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a second file of the suite describes the chip (test_cells_lower_for_
    # tpu.py), under xdist in another process: take no libtpu lock here
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_fwd_bwd(dtype, d, dv, g, sharding, block_q=None, qr=None):
    """Forward and backward of a causal call over ``qr`` (one document of
    ``S`` tokens) at ``block_q`` x the default ``block_k`` (None: whatever
    an unpinned call chooses), compiled for ``sharding``'s chip."""
    from magiattention_tpu.kernels import ffa

    qr = np.array([[0, S]], np.int32) if qr is None else qr
    tokens = int(qr[-1, 1])

    def loss(q, k, v):
        out, _ = ffa.ffa_attn(
            q, k, v, qr, qr, np.ones(len(qr), np.int32), block_q=block_q)
        return out.astype(jnp.float32).sum()

    args = [jax.ShapeDtypeStruct((tokens, h, e), getattr(jnp, dtype),
                                 sharding=sharding)
            for h, e in ((g, d), (1, d), (1, dv))]
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile()


def _sweep_case(dtype, d, dv, g):
    marks = ()
    if (dtype, d, dv, g) in DKV_REFUSED:
        marks = pytest.mark.xfail(
            strict=True, raises=jax.errors.JaxRuntimeError,
            reason="packed dkv at 2048 rows: admitted, refused (parent's)")
    return pytest.param(dtype, d, dv, g, marks=marks,
                        id=f"{dtype}-d{d}v{dv}-g{g}")


@pytest.mark.parametrize("dtype,d,dv,g", [
    _sweep_case(dtype, d, dv, g) for dtype, (d, dv), g
    in itertools.product(DTYPES, HEAD_DIMS, GROUPS)])
def test_what_the_guard_admits_compiles_for_v5e(
    compiled_kernels, one_chip, dtype, d, dv, g
):
    """The 256-row tile (the default up to g = 4, a pin at g = 8): every
    pass packed that ``gqa_pack_fits`` admits, the rest plain, and the
    chip's compiler takes all of it."""
    from magiattention_tpu.kernels import ffa, registry

    compiled = _compile_fwd_bwd(
        dtype, d, dv, g, one_chip, block_q=256 if g > 4 else None)
    assert "tpu_custom_call" in compiled.as_text()
    itemsize = jnp.dtype(dtype).itemsize
    assert registry.last_choice("ffa_tiles") == " ".join(
        f"{kind}256x512" + (
            f"g{g}" if ffa.gqa_pack_fits(kind, g, 256, 512, d, dv, itemsize)
            else "")
        for kind in ("fwd", "dq", "dkv"))


@pytest.mark.parametrize("dtype,d,dv,g,kind,bq", [
    *((*case, 256) for case in Q_MAJOR_REFUSED),
    *((*case, RULE_TILES[case[3]]) for case in Q_MAJOR_REFUSED_AT_RULE_TILES),
])
def test_what_the_compiler_refused_the_guard_keeps_out(
    compiled_kernels, one_chip, monkeypatch, dtype, d, dv, g, kind, bq
):
    """The reason for the q-major bounds: under the plain VMEM budget the
    guard admits this packed step and the compiler refuses it. The day it
    compiles, the bounds can loosen."""
    from magiattention_tpu.kernels import ffa

    itemsize = jnp.dtype(dtype).itemsize
    assert not ffa.gqa_pack_fits(kind, g, bq, 512, d, dv, itemsize)
    monkeypatch.setattr(ffa, "Q_MAJOR_PACK_MAX_ROWS", 1 << 30)
    monkeypatch.setattr(ffa, "Q_MAJOR_PACK_MAX_BYTES", ffa.VMEM_ALLOWED_BYTES)
    assert ffa.gqa_pack_fits(kind, g, bq, 512, d, dv, itemsize)
    for pass_, flag in FLAGS.items():  # this pass packed, alone
        monkeypatch.setenv(flag, "1" if pass_ == kind else "0")
    with pytest.raises(jax.errors.JaxRuntimeError, match="vmem"):
        _compile_fwd_bwd(dtype, d, dv, g, one_chip, block_q=bq)


@pytest.mark.parametrize("bwd", ["split", "fused"])
@pytest.mark.parametrize("dtype,d,dv,g", [
    pytest.param(dtype, d, dv, g, id=f"{dtype}-d{d}v{dv}-g{g}")
    for dtype, (d, dv), g in itertools.product(DTYPES, HEAD_DIMS, RULE_TILES)])
def test_what_the_group_rule_chooses_compiles_for_v5e(
    compiled_kernels, one_chip, monkeypatch, dtype, d, dv, g, bwd
):
    """An unpinned call at g = 8 and g = 16: where the rule moves
    ``block_q`` every pass packs, 1024 rows a step, and the chip's compiler
    takes the forward, dq, dkv and one-pass bodies there; where a body does
    not fit the 256-row tile stays, with the bodies it had."""
    from magiattention_tpu.kernels import ffa, registry

    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_FFA_BWD", bwd)
    itemsize = jnp.dtype(dtype).itemsize
    kinds = ("fwd", "dq", "dkv", "fused")
    moved = all(
        ffa.gqa_pack_fits(kind, g, RULE_TILES[g], 512, d, dv, itemsize)
        for kind in kinds)
    # the same step as g = 4 at 256 rows: it moves where that packs whole
    # (not at d = 256, nor in float32 at qk 192, where the packed dq is
    # over the byte budget)
    assert moved == all(
        ffa.gqa_pack_fits(kind, 4, 256, 512, d, dv, itemsize)
        for kind in kinds)
    assert moved == (d < 192 or (d, dtype) == (192, "bfloat16"))
    compiled = _compile_fwd_bwd(dtype, d, dv, g, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    bq = RULE_TILES[g] if moved else 256
    assert registry.last_choice("ffa_tiles") == " ".join(
        f"{kind}{bq}x512" + (
            f"g{g}" if ffa.gqa_pack_fits(kind, g, bq, 512, d, dv, itemsize)
            else "")
        for kind in ("fwd", "dq", "dkv"))
    assert registry.last_source("ffa_tiles") == (
        "shape_rule" if moved else "default")
    assert registry.last_choice("ffa_bwd") == bwd


@pytest.mark.parametrize("bq", [256, 64])
@pytest.mark.parametrize("dtype,d,dv", [
    (dtype, d, dv) for dtype, (d, dv) in itertools.product(DTYPES, HEAD_DIMS)])
def test_sixteen_heads_a_group_never_pack(dtype, d, dv, bq):
    """4096 packed rows fit no budget: at g = 16 and the 256-row tile every
    pass runs plain. At 64 rows (the rule's tile) a step is 1024 rows as at
    g = 4 x 256, and packs wherever that does."""
    from magiattention_tpu.kernels import ffa

    itemsize = jnp.dtype(dtype).itemsize
    kinds = ("fwd", "dq", "dkv", "fused")
    packs = [ffa.gqa_pack_fits(kind, 16, bq, 512, d, dv, itemsize)
             for kind in kinds]
    if bq == 256:
        assert not any(packs)
    else:
        assert packs == [ffa.gqa_pack_fits(kind, 4, 256, 512, d, dv, itemsize)
                         for kind in kinds]


def _documents_of_work(w: int, bq: int, bk: int) -> np.ndarray:
    """Causal documents whose q-major and k-major lists are ``w`` work
    items at ``bq`` x ``bk``: one of whole q tiles, then one-tile ones."""
    tiles = items = 0
    while items + (tiles * bq + bq - 1) // bk + 1 <= w:
        items += (tiles * bq + bq - 1) // bk + 1
        tiles += 1
    ends = [tiles * bq + i * bq for i in range(w - items + 1)]
    return np.array(list(zip([0, *ends[:-1]], ends)), np.int32)


@pytest.mark.parametrize("bwd", ["split", "fused"])
@pytest.mark.parametrize("over", [
    pytest.param(0, id="at_the_capacity"),
    pytest.param(32, id="32_items_over")])
def test_the_plan_table_capacity_is_the_compilers(
    compiled_kernels, one_chip, monkeypatch, over, bwd
):
    """``ffa.PLAN_TABLE_MAX_WORK``: a plan of that many work items (both
    lists) compiles, the packed forward and both backwards at g = 8 and
    128 x 512; 32 items more (2016 rows of 512 bytes, past the 2008 the
    bisection found) are refused for SMEM. The day they compile, the
    constant can grow and more calls take the rule's tile."""
    from magiattention_tpu.kernels import ffa, tile_policy
    from magiattention_tpu.kernels.mask_utils import types_to_bands

    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_FFA_BWD", bwd)
    work = ffa.PLAN_TABLE_MAX_WORK + over
    qr = _documents_of_work(work, 128, 512)
    tokens = int(qr[-1, 1])
    lo, hi = types_to_bands(qr, qr, np.ones(len(qr), np.int32))
    geom = (qr, qr, lo, hi, tokens, tokens, 128, 512)
    assert tile_policy.count_ffa_work(*geom) == work
    assert tile_policy.count_ffa_work_t(*geom) == work
    if over:
        with pytest.raises(jax.errors.JaxRuntimeError, match="smem"):
            _compile_fwd_bwd(
                "bfloat16", 128, 128, 8, one_chip, block_q=128, qr=qr)
        return
    compiled = _compile_fwd_bwd(
        "bfloat16", 128, 128, 8, one_chip, block_q=128, qr=qr)
    assert "tpu_custom_call" in compiled.as_text()
