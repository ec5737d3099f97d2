"""What ``ffa.gqa_pack_fits`` admits, the v5e compiler takes — and what the
compiler refused, the guard keeps out.

The packed q-major bodies (fwd, dq) are on by default wherever there is a
GQA group (PR 25), so ``gqa_pack_fits`` is all that stands between a
default call and a Mosaic VMEM refusal. Its two q-major bounds
(``Q_MAJOR_PACK_MAX_ROWS``, ``Q_MAJOR_PACK_MAX_BYTES``) were fitted to a
compile sweep; this file is that sweep: g x head dims x dtype at the
default 256 x 512 tiles, forward and backward, compiled for one chip of a
described ``v5e:2x2`` from the CPU, as
``tests/test_cellbench/test_cells_lower_for_tpu.py`` does for the cells'
shapes (VMEM is checked there and costs no chip time). Skipped where no
such topology can be described.
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


def _compile_fwd_bwd(dtype, d, dv, g, sharding):
    from magiattention_tpu.kernels import ffa

    qr = np.array([[0, S]], np.int32)

    def loss(q, k, v):
        out, _ = ffa.ffa_attn(q, k, v, qr, qr, np.array([1], np.int32))
        return out.astype(jnp.float32).sum()

    args = [jax.ShapeDtypeStruct((S, h, e), getattr(jnp, dtype),
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
    """A default call: every pass packed that ``gqa_pack_fits`` admits,
    the rest plain, and the chip's compiler takes all of it."""
    from magiattention_tpu.kernels import ffa, registry

    compiled = _compile_fwd_bwd(dtype, d, dv, g, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    itemsize = jnp.dtype(dtype).itemsize
    assert registry.last_choice("ffa_tiles") == " ".join(
        f"{kind}256x512" + (
            f"g{g}" if ffa.gqa_pack_fits(kind, g, 256, 512, d, dv, itemsize)
            else "")
        for kind in ("fwd", "dq", "dkv"))


@pytest.mark.parametrize("dtype,d,dv,g,kind", Q_MAJOR_REFUSED)
def test_what_the_compiler_refused_the_guard_keeps_out(
    compiled_kernels, one_chip, monkeypatch, dtype, d, dv, g, kind
):
    """The reason for the q-major bounds: under the plain VMEM budget the
    guard admits this packed step and the compiler refuses it. The day it
    compiles, the bounds can loosen."""
    from magiattention_tpu.kernels import ffa

    itemsize = jnp.dtype(dtype).itemsize
    assert not ffa.gqa_pack_fits(kind, g, 256, 512, d, dv, itemsize)
    monkeypatch.setattr(ffa, "Q_MAJOR_PACK_MAX_ROWS", 1 << 30)
    monkeypatch.setattr(ffa, "Q_MAJOR_PACK_MAX_BYTES", ffa.VMEM_ALLOWED_BYTES)
    assert ffa.gqa_pack_fits(kind, g, 256, 512, d, dv, itemsize)
    for pass_, flag in FLAGS.items():  # this pass packed, alone
        monkeypatch.setenv(flag, "1" if pass_ == kind else "0")
    with pytest.raises(jax.errors.JaxRuntimeError, match="vmem"):
        _compile_fwd_bwd(dtype, d, dv, g, one_chip)


@pytest.mark.parametrize("dtype,d,dv", [
    (dtype, d, dv) for dtype, (d, dv) in itertools.product(DTYPES, HEAD_DIMS)])
def test_sixteen_heads_a_group_never_pack(dtype, d, dv):
    """4096 packed rows fit no budget: at g = 16 every pass runs plain."""
    from magiattention_tpu.kernels import ffa

    itemsize = jnp.dtype(dtype).itemsize
    assert not any(ffa.gqa_pack_fits(kind, 16, 256, 512, d, dv, itemsize)
                   for kind in ("fwd", "dq", "dkv", "fused"))
