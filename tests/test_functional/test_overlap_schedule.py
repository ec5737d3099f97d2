"""Overlap schedule evidence from the lowered program, not vibes (VERDICT r2).

The multi-stage CP path claims XLA hides stage-i+1's GroupCast under
stage-i's kernel (functional/dist_attn.py: "issue every stage's collective
up front"). The necessary condition is checkable without a chip: in the
TPU-lowered program, every stage's collective must be *issued before the
first FFA kernel custom call* — i.e. the collectives have no data
dependence on kernel output and the emission order lets XLA's async
scheduler overlap them.

Limits (documented): the async start/done split + latency-hiding schedule
happen inside the TPU compiler (needs libtpu); XLA:CPU never splits
collectives into async pairs (verified: compiled CPU HLO of this exact
program contains zero `-start`/`-done` ops), so the *scheduled* overlap can
only be measured on the chip (benchmarks/overlap_bench.py on a four-chip
host; ROADMAP A6).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from magiattention_tpu import DistAttnConfig, OverlapConfig
from magiattention_tpu.api import calc_attn, dispatch, magi_attn_flex_key
from magiattention_tpu.kernels import ffa

S, H, HK, D = 512, 2, 1, 32
CP = 4

_COLLECTIVE_RE = re.compile(
    r"stablehlo\.(all_to_all|collective_permute)|ragged_all_to_all"
)
_KERNEL_RE = re.compile(r"tpu_custom_call")


@pytest.fixture()
def mosaic(monkeypatch):
    from magiattention_tpu.functional import dist_attn

    monkeypatch.setattr(ffa, "_should_interpret", lambda: False)
    monkeypatch.setattr(dist_attn, "_should_interpret", lambda: False)


def _lowered_text(degree: int) -> str:
    mesh = Mesh(np.array(jax.devices("cpu")[:CP]), ("cp",))
    cfg = DistAttnConfig(overlap_config=OverlapConfig(degree=degree))
    key = magi_attn_flex_key(
        [[0, S]], [[0, S]], [1], S, S,
        mesh=mesh, cp_axis="cp", chunk_size=32, dist_attn_config=cfg,
    )
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.float32)
    qd = dispatch(q, key)
    kd = dispatch(k, key, role="kv")
    vd = dispatch(v, key, role="kv")

    def f(q, k, v):
        out, _ = calc_attn(q, k, v, key)
        return out

    return (
        jax.jit(f).trace(qd, kd, vd)
        .lower(lowering_platforms=("tpu",))
        .as_text()
    )


@pytest.mark.parametrize("degree", [1, 2])
def test_stage_collectives_issue_before_kernels(mosaic, degree):
    text = _lowered_text(degree)
    coll_pos = [m.start() for m in _COLLECTIVE_RE.finditer(text)]
    kern_pos = [m.start() for m in _KERNEL_RE.finditer(text)]
    assert coll_pos, "expected GroupCast collectives in the lowered program"
    assert kern_pos, "expected FFA kernel custom calls"
    first_kernel = min(kern_pos)
    late = [p for p in coll_pos if p > first_kernel]
    assert not late, (
        f"{len(late)}/{len(coll_pos)} stage collectives are issued after "
        f"the first FFA kernel — the up-front issue order (the overlap "
        f"precondition) regressed"
    )


def test_multi_stage_has_per_stage_collectives(mosaic):
    """degree=2 must produce more collective issues than degree=1 (the
    stages really are separate transfers, not one merged cast)."""
    n1 = len(_COLLECTIVE_RE.findall(_lowered_text(1)))
    n2 = len(_COLLECTIVE_RE.findall(_lowered_text(2)))
    assert n2 > n1, (n1, n2)
