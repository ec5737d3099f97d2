"""Every ``pallas_call`` of the FFA is bound under its kernel body's name.

XLA names a ``tpu_custom_call`` instruction after the innermost named scope
of its ``op_name``, so ``kernels/_named.py`` binds every Pallas call under
``jax.named_scope("magi" + body.__name__)``: a device trace shows
``%magi_fwd_kernel.1``, ``%magi_bwd_dq_kernel.1``, ... On the CPU the same
scope is the equation's ``source_info.name_stack``, which is what these
tests read. They also pin what must NOT change with it: the body's own
``debug_info.func_name`` and the custom call's ``kernel_name`` (the
benchmark's and ``chip_smoke.py``'s kernel reports read the first, and the
benchmark's ``correct`` needs a kernel called ``_fwd_kernel*``).

Nothing here sets ``MAGI_ATTENTION_PROFILE_MODE``: the names are not gated.
The kernel variants are reached through their own switches, as
``test_mosaic_lowering.py`` reaches them.
"""

from __future__ import annotations

import json
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.kernels import _named, ffa

ALL_BODIES = {
    "_fwd_kernel", "_fwd_kernel_gqa", "_delta_kernel",
    "_bwd_dq_kernel", "_bwd_dq_kernel_gqa",
    "_bwd_dkv_kernel", "_bwd_dkv_kernel_gqa",
    "_bwd_fused_kernel", "_bwd_fused_kernel_gqa",
}

# (id, group size, switches) -> the bodies the forward+backward must hold.
# Every pass packs by default where there is a group (PR 25).
PLAIN_Q_MAJOR = {"MAGI_ATTENTION_FFA_GQA_PACK": "0",
                 "MAGI_ATTENTION_FFA_GQA_PACK_DQ": "0"}
VARIANTS = [
    ("split_default", 2, {"MAGI_ATTENTION_BACKEND_FFA_BWD": "split"},
     {"_fwd_kernel_gqa", "_delta_kernel", "_bwd_dq_kernel_gqa",
      "_bwd_dkv_kernel_gqa"}),
    ("split_mha", 1, {"MAGI_ATTENTION_BACKEND_FFA_BWD": "split"},
     {"_fwd_kernel", "_delta_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel"}),
    ("split_plain_q_major", 2,
     {"MAGI_ATTENTION_BACKEND_FFA_BWD": "split", **PLAIN_Q_MAJOR},
     {"_fwd_kernel", "_delta_kernel", "_bwd_dq_kernel",
      "_bwd_dkv_kernel_gqa"}),
    ("fused_gqa_packed", 2, {"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused"},
     {"_fwd_kernel_gqa", "_delta_kernel", "_bwd_fused_kernel_gqa"}),
    # the plain one-pass body walks the group innermost: a head's dq window
    # comes back every g steps, and a group of 2 is under the distance
    ("fused_plain", 4,
     {"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused",
      "MAGI_ATTENTION_FFA_GQA_PACK_DKV": "0", **PLAIN_Q_MAJOR},
     {"_fwd_kernel", "_delta_kernel", "_bwd_fused_kernel"}),
]


@pytest.fixture()
def bare_env(monkeypatch):
    """No ``MAGI_ATTENTION_*`` variable at all (the suite's conftest sets
    two): on the CPU the kernels are interpreted by themselves."""
    for key in [k for k in os.environ if k.startswith("MAGI_ATTENTION_")]:
        monkeypatch.delenv(key)
    return monkeypatch


def _pallas_eqns(jaxpr) -> list:
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found.extend(_pallas_eqns(inner))
    return found


def _grad_fn(g: int):
    s, hk, d = 1024, 2, 128
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((s, h, d)), jnp.bfloat16)
        for h in (hk * g, hk, hk))
    bounds = [0, s // 4, (2 * s) // 3, s]
    qr = np.array(list(zip(bounds[:-1], bounds[1:])), np.int32)
    tm = np.array([1, 0, 1], np.int32)

    def loss(q, k, v):
        out, _ = ffa.ffa_attn(q, k, v, qr, qr.copy(), tm)
        return jnp.sum(out.astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2)), (q, k, v)


def _group_comm_name_pattern() -> re.Pattern:
    """The pattern of ``event_classes.json`` that claims an instruction for
    ``group_comm`` by its name (anchored at the name's start)."""
    path = os.path.join(
        os.path.dirname(__file__), "..", "..", "cellbench",
        "event_classes.json")
    with open(path, encoding="utf-8") as f:
        classes = {c["class"]: c["patterns"] for c in json.load(f)["classes"]}
    (by_name,) = [p for p in classes["group_comm"] if p.startswith("^")]
    return re.compile(by_name)


@pytest.mark.parametrize(
    "g,switches,bodies", [v[1:] for v in VARIANTS],
    ids=[v[0] for v in VARIANTS])
def test_every_pallas_call_is_bound_under_its_bodys_name(
    bare_env, g, switches, bodies
):
    for key, value in switches.items():
        bare_env.setenv(key, value)
    fn, args = _grad_fn(g)
    eqns = _pallas_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
    seen = set()
    for eqn in eqns:
        body = eqn.params["jaxpr"].debug_info.func_name
        seen.add(body)
        # the scope, decorated by the transforms it was traced under:
        # ``jvp(magi_fwd_kernel)``, ``transpose(jvp(magi_delta_kernel))``
        stack = str(eqn.source_info.name_stack)
        assert re.search(rf"\bmagi{body}\b", stack), (body, stack)
    # the bodies keep their own names: a kernel report reads them
    assert seen == bodies


@pytest.mark.parametrize(
    "g,switches,bodies", [v[1:] for v in VARIANTS],
    ids=[v[0] for v in VARIANTS])
def test_the_custom_calls_kernel_name_is_unchanged(
    bare_env, g, switches, bodies
):
    """The module differs from the unnamed one in metadata only: Mosaic's
    ``kernel_name`` is still the body's own name."""
    for key, value in switches.items():
        bare_env.setenv(key, value)
    bare_env.setattr(ffa, "_should_interpret", lambda: False)
    fn, args = _grad_fn(g)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert set(re.findall(r'kernel_name = "([^"]+)"', text)) == bodies
    assert text.count("tpu_custom_call") == len(bodies)


def test_the_variants_cover_every_ffa_body():
    assert set().union(*(v[3] for v in VARIANTS)) == ALL_BODIES
    for name in ALL_BODIES:
        assert callable(getattr(ffa, name))


@pytest.mark.parametrize("body", sorted(ALL_BODIES))
def test_scope_name_is_the_bodys_name_behind_the_prefix(body):
    fn = getattr(ffa, body)
    want = "magi" + body
    assert _named.kernel_scope_name(fn) == want
    # taken from the kernel function itself, through any partial
    assert _named.kernel_scope_name(partial(partial(fn, bq=8), bk=8)) == want
    # clear of the names by which a trace knows the library's collectives
    assert not _group_comm_name_pattern().search(want)
    # XLA turns a scope into an instruction name as it is: nothing in it
    # may need escaping
    assert re.fullmatch(r"[A-Za-z0-9_]+", want)


def test_no_pallas_call_site_passes_a_name():
    """``pl.pallas_call(..., name=X)`` opens the same scope but overwrites
    the body's ``func_name`` with X, which would turn every cell of the
    benchmark ``correct: false``."""
    import ast
    import pathlib

    kernels = pathlib.Path(ffa.__file__).parent
    sites = 0
    for path in kernels.glob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                sites += 1
                assert "name" not in {k.arg for k in node.keywords}, (
                    path.name, node.lineno)
                if path.name != "_named.py":
                    # every site goes through the helper
                    assert ast.unparse(node.func) == "_named.pallas_call", (
                        path.name, node.lineno)
    # 9 FFA + 3 paged decode + 2 block sparse + 2 scan + 2 grouped matmul + helper
    assert sites == 19


def test_the_benchmarks_kernel_report_is_unchanged(bare_env):
    """``cellbench.family_llama.pallas_kernels`` of a toy ``train_step``:
    the set the cells report, name for name — the packed bodies since
    PR 25, where PR 22's and PR 24's runs had the plain fwd and dq, and
    the one-pass backward since PR 30, where the split pair ran."""
    from jax.sharding import Mesh

    from cellbench import family_llama as family
    from cellbench.traffic_gen import MaskSpec

    cfg = {**family.TOY, "rope_theta": 1e4, "rms_norm_eps": 1e-5}
    mcfg = family.model_config(cfg)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("cp",))
    key = family.make_key(MaskSpec(tokens=512, cu_seqlens=(0, 512)), mesh)
    params = jax.eval_shape(
        partial(family.init_params, mcfg, mesh), 0)
    tokens = jax.ShapeDtypeStruct((512,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t, l: family.train_step(p, mcfg, t, l, key)
    )(params, tokens, tokens)
    assert family.pallas_kernels(jaxpr) == {
        "_fwd_kernel_gqa": True, "_delta_kernel": True,
        "_bwd_fused_kernel_gqa": True,
    }
    stacks = {
        eqn.params["jaxpr"].debug_info.func_name:
            str(eqn.source_info.name_stack)
        for eqn in _pallas_eqns(jaxpr.jaxpr)}
    assert all("magi" + body in stack for body, stack in stacks.items())
