"""High-precision wire reduce A/B (ref MAGI_ATTENTION_BACKWARD_HIGH_
PRECISION_REDUCE, env/comm.py:123; _reduce_partial_dkv, dist_attn.py:2123).

The static CP runtime's backward reduces partial dkv across ranks through
the AD transpose of the forward GroupCast. By default that wire carries the
compute dtype (bf16); with the flag on, hp_group_cast keeps the partials
fp32 through the collective and casts only after the cross-rank sum —
removing the cp-way low-precision summation error at 2x backward comm
bytes. These tests pin (a) the traced wire dtype actually changes, (b) both
modes remain correct, and (c) at bf16 cp=8 the hp grads are at least as
close to an fp32 oracle (the quantified delta the flag buys).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from magiattention_tpu.api import calc_attn, dispatch, magi_attn_flex_key

S, HQ, HK, D = 256, 4, 2, 32
CP = 8


def _mesh():
    return Mesh(np.array(jax.devices("cpu")[:CP]), ("cp",))


def _data(dtype):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((S, HQ, D)), dtype)
    k = jnp.asarray(rng.standard_normal((S, HK, D)), dtype)
    v = jnp.asarray(rng.standard_normal((S, HK, D)), dtype)
    w = jnp.asarray(rng.standard_normal((S, HQ, D)), dtype)
    return q, k, v, w


def _grads(monkeypatch, hp: bool, dtype=jnp.bfloat16):
    monkeypatch.setenv(
        "MAGI_ATTENTION_BWD_HIGH_PRECISION_REDUCE", "1" if hp else "0"
    )
    mesh = _mesh()
    key = magi_attn_flex_key(
        [[0, S]], [[0, S]], [1], S, S, mesh=mesh, chunk_size=16
    )
    q, k, v, w = _data(dtype)

    def loss(q, k, v):
        qd = dispatch(q, key)
        kd = dispatch(k, key, role="kv")
        vd = dispatch(v, key, role="kv")
        od, _ = calc_attn(qd, kd, vd, key)
        return jnp.sum(od.astype(jnp.float32) * dispatch(w, key).astype(jnp.float32))

    gfn = jax.grad(loss, argnums=(0, 1, 2))
    hlo = jax.jit(gfn).lower(q, k, v).as_text()
    return gfn(q, k, v), hlo


@pytest.mark.slow
def test_hp_flag_changes_wire_dtype(monkeypatch):
    """With the flag on, at least one backward collective carries f32."""
    _, hlo_lp = _grads(monkeypatch, hp=False)
    _, hlo_hp = _grads(monkeypatch, hp=True)

    def f32_collectives(hlo: str) -> int:
        # stablehlo collective lines carry their result type inline, e.g.
        # `"stablehlo.all_to_all"(...) ... -> tensor<...xf32>`
        return len(
            re.findall(
                r"all_to_all[^\n]*xf32>|collective_permute[^\n]*xf32>", hlo
            )
        )

    assert f32_collectives(hlo_hp) > f32_collectives(hlo_lp)


@pytest.mark.slow
def test_hp_matches_lp_within_bf16_tol(monkeypatch):
    (dq_lp, dk_lp, dv_lp), _ = _grads(monkeypatch, hp=False)
    (dq_hp, dk_hp, dv_hp), _ = _grads(monkeypatch, hp=True)
    for a, b in ((dq_lp, dq_hp), (dk_lp, dk_hp), (dv_lp, dv_hp)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.1, atol=0.5,
        )


@pytest.mark.slow
def test_hp_reduce_at_least_as_accurate(monkeypatch):
    """bf16 cp=8 vs an fp32 end-to-end oracle: the hp dk/dv error must not
    exceed the lp error (the delta the 2x comm bytes buy)."""
    (_, dk_lp, dv_lp), _ = _grads(monkeypatch, hp=False)
    (_, dk_hp, dv_hp), _ = _grads(monkeypatch, hp=True)
    (_, dk_or, dv_or), _ = _grads(monkeypatch, hp=False, dtype=jnp.float32)

    def err(g, ref):
        g = np.asarray(g, np.float64)
        ref = np.asarray(ref, np.float64)
        return float(np.linalg.norm(g - ref) / (np.linalg.norm(ref) + 1e-30))

    e_lp = err(dk_lp, dk_or) + err(dv_lp, dv_or)
    e_hp = err(dk_hp, dk_or) + err(dv_hp, dv_or)
    print(f"hp-reduce A/B @bf16 cp=8: err_lp={e_lp:.5f} err_hp={e_hp:.5f}")
    assert e_hp <= e_lp * 1.02 + 1e-6


@pytest.mark.slow
@pytest.mark.parametrize("flag", ["0", "1"])
def test_dynamic_runtime_consumes_flags(monkeypatch, flag):
    """qo-comm path: both HP flags produce correct out/grads (the dynamic
    runtime reduces partial dq/dkv explicitly; flag picks the wire dtype)."""
    monkeypatch.setenv("MAGI_ATTENTION_QO_COMM", "1")
    monkeypatch.setenv("MAGI_ATTENTION_FWD_HIGH_PRECISION_REDUCE", flag)
    monkeypatch.setenv("MAGI_ATTENTION_BWD_HIGH_PRECISION_REDUCE", flag)
    mesh = _mesh()
    key = magi_attn_flex_key(
        [[0, S]], [[0, S]], [1], S, S, mesh=mesh, chunk_size=16
    )
    q, k, v, w = _data(jnp.float32)

    def loss(q, k, v):
        qd = dispatch(q, key)
        kd = dispatch(k, key, role="kv")
        vd = dispatch(v, key, role="kv")
        od, _ = calc_attn(qd, kd, vd, key)
        return jnp.sum(od * dispatch(w, key))

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # fp32 oracle through the dense sdpa backend (exact mask replay)
    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "sdpa")
    key2 = magi_attn_flex_key(
        [[0, S]], [[0, S]], [1], S, S, mesh=mesh, chunk_size=16
    )

    def loss2(q, k, v):
        qd = dispatch(q, key2)
        kd = dispatch(k, key2, role="kv")
        vd = dispatch(v, key2, role="kv")
        od, _ = calc_attn(qd, kd, vd, key2)
        return jnp.sum(od * dispatch(w, key2))

    g_ref = jax.grad(loss2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        )


def test_hp_group_cast_primitive_fast():
    """Fast-tier coverage of hp_group_cast itself: fp32 output, fp32
    collective in the backward HLO, and gradients equal to the plain cast
    (the e2e runtime A/Bs above are the slow tier)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from magiattention_tpu.comm.primitives import cast_rows
    from magiattention_tpu.functional.dist_attn import hp_group_cast

    cp, shard = 8, 4
    mesh = _mesh()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((cp * shard, 8)), jnp.bfloat16)
    # every rank broadcasts its row 0 to all ranks (simple dense plan)
    send_idx = np.zeros((cp, 1), np.int32)
    recv_sel = np.arange(cp, dtype=np.int32)  # one row from each src
    ops = (jnp.asarray(send_idx), jnp.asarray(recv_sel))

    def make(f):
        def shard_fn(x, ops):
            return jnp.sum(
                f(x, tuple(o for o in ops)).astype(jnp.float32) ** 2
            )

        def loss(x):
            return jnp.sum(shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P("cp"), (P(), P())), out_specs=P(),
                check_vma=False,
            )(x, ops))

        return loss

    hp = make(lambda x, o: hp_group_cast(
        x, o, ("a2a",), "cp", shard, x.dtype.name))
    lp = make(lambda x, o: cast_rows(x, o, ("a2a",), "cp"))

    g_hp = jax.grad(hp)(x)
    g_lp = jax.grad(lp)(x)
    assert g_hp.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(g_hp, np.float32), np.asarray(g_lp, np.float32),
        rtol=1e-2, atol=1e-2,
    )
    hlo = jax.jit(jax.grad(hp)).lower(x).as_text()
    assert re.search(r"all_to_all[^\n]*xf32>", hlo), "no fp32 wire reduce"
