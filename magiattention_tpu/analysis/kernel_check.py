"""Static Pallas kernel contract checker (rules K1-K5).

PR 3's verifier proves plan-level invariants (R1-R5); this module proves
the KERNEL level: every ``pl.pallas_call`` site under ``kernels/`` is
discovered by AST, its contract (grid, BlockSpec shapes + index_maps,
scratch, dtypes) is reconstructed by interception — the wrapper functions
are driven with real plans and dummy operands while ``pallas_call`` is
replaced by a recorder, so the kernel bodies never execute — and the
contract is checked against five rule families:

- **K1** VMEM budget: the exact per-step residency (double-buffered
  in/out blocks + scratch + score-tile intermediates) fits the per-core
  budget with headroom. ONE model backs every layer:
  ``utils/mem_budget.ffa_kernel_residency`` is asserted here to match the
  captured contracts bit-for-bit, the packed-kernel dispatch guards in
  ``kernels/ffa.py`` call it, and the tile policy's candidate filter
  (guarded by the ``vmem_check`` fault-injection site) is asserted equal
  to ``mem_budget.ffa_vmem_budget``/``ffa_bwd_vmem_budget``. The
  abstract sweep (:func:`check_reachable_space`) closes the proof over
  the FULL config space ``tile_policy.reachable_block_space`` can emit —
  not just the sampled corpus.
- **K2** accumulator discipline (source-level, driven by
  ``kernels/ffa.py:PALLAS_CONTRACTS``): every cross-step scratch
  accumulator is zero-initialized under the is-first guard — qualified
  on the innermost grid position when the grid revisits tiles — and
  every output ref is stored exactly once, under the is-last guard (the
  dkv-GQA-pack bug class).
- **K3** index-map bounds: every index_map output x block shape stays
  inside its operand for ALL grid points (vectorized numpy evaluation of
  the captured index_map lambdas over the whole grid). The extent half
  (:func:`check_k3_extents`) proves the EQ0..EK1 live-extent prefetch
  columns — the state the clamp path skips dot chunks on — match a host
  recomputation from the band geometry and respect tile bounds and the
  sublane/lane chunking quanta.
- **K4** dtype/precision: fp32 accumulator scratch, fp32-preferred
  ``dot_general``s, declared out dtypes honored (no implicit f32->bf16
  truncation before the final guarded write).
- **K5** cache-key soundness: every env key consumed under ``kernels/``
  appears in ``ENV_KEYS_AFFECTING_RUNTIME`` or the audited allowlist of
  keys proven not to change lowering.

Violations reuse the :mod:`violation` registry; ``scripts/kernel_audit.py``
sweeps the golden corpus and ``make kernel-audit`` gates ``make test`` on
a clean run. See docs/kernel_contracts.md.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field, replace  # noqa: F401 (replace: test API)
from pathlib import Path

import numpy as np

from ..kernels.ffa_plan import (
    EK1,
    EQ0,
    LANE_QUANTUM,
    META_DIM,
    QE,
    QS,
    SUBLANE_QUANTUM,
    _extend_meta_extents,
)
from ..kernels.tile_policy import VMEM_BUDGET as POLICY_VMEM_BUDGET
from ..utils.mem_budget import (
    VMEM_ALLOWED_BYTES,
    VMEM_HEADROOM_BYTES,
    VMEM_LIMIT_BYTES,
    ffa_bwd_vmem_budget,
    ffa_kernel_residency,
    ffa_vmem_budget,
)
from .violation import ERROR, VerifyReport

__all__ = [
    "AuditSpec",
    "KernelContract",
    "PallasSite",
    "POLICY_VMEM_BUDGET",
    "VMEM_ALLOWED_BYTES",
    "VMEM_HEADROOM_BYTES",
    "VMEM_LIMIT_BYTES",
    "K5_ALLOWLIST",
    "bwd_vmem_bytes",
    "capture_ffa_contracts",
    "check_contract",
    "check_env_keys",
    "check_k3_extents",
    "check_kernel_sources",
    "check_reachable_space",
    "discover_pallas_sites",
    "fwd_vmem_bytes",
    "golden_corpus",
    "padding_stats",
    "run_kernel_audit",
    "run_seeded_mutations",
]

# env keys consumed under kernels/ that are PROVEN not to change kernel
# lowering and are therefore exempt from ENV_KEYS_AFFECTING_RUNTIME
# membership (K5). Every entry carries its proof obligation.
K5_ALLOWLIST: dict[str, str] = {
    "MAGI_ATTENTION_NATIVE_FFA_PLAN": (
        "selects the native-C vs pure-Python FFA plan builder; both emit "
        "identical work-item arrays (parity pinned by the plan tests), so "
        "the traced kernel program cannot differ"
    ),
}


# ---------------------------------------------------------------------------
# the shared VMEM model (verifier R5 delegates here — satellite 3)
# ---------------------------------------------------------------------------


def fwd_vmem_bytes(
    bq: int, bk: int, d: int, dv: int | None = None, itemsize: int = 2
) -> int:
    """Estimated fwd per-step residency — the tile policy's filter model."""
    return ffa_vmem_budget(bq, bk, d, head_dim_v=dv, dtype_bytes=itemsize)


def bwd_vmem_bytes(
    kind: str, bq: int, bk: int, d: int, dv: int | None = None,
    itemsize: int = 2,
) -> int:
    """Estimated bwd per-step residency — the tile policy's filter model."""
    return ffa_bwd_vmem_budget(
        kind, bq, bk, d, head_dim_v=dv, dtype_bytes=itemsize
    )


# ---------------------------------------------------------------------------
# discovery (AST)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PallasSite:
    """One ``pl.pallas_call`` site in the kernels package."""

    relpath: str
    line: int
    wrapper: str  # enclosing function
    kernel_name: str  # the kernel body passed (resolved through partial)


def _kernels_dir() -> Path:
    return Path(__file__).resolve().parents[1] / "kernels"


def discover_pallas_sites(kernels_dir: str | Path | None = None) -> list[PallasSite]:
    """Every ``*.pallas_call`` call site under ``kernels/`` (the sites go
    through ``_named.pallas_call``, which forwards to ``pl.pallas_call``
    under the kernel's name and is itself no site), with the kernel body
    name resolved through local ``kernel = partial(<fn>, ...)`` assignments
    inside the enclosing wrapper."""
    root = Path(kernels_dir) if kernels_dir else _kernels_dir()
    sites: list[PallasSite] = []
    for path in sorted(root.glob("*.py")):
        if path.name == "_named.py":
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            partials: dict[str, str] = {}
            for node in ast.walk(fn):
                if (
                    isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)
                    and _callee_name(node.value.func) == "partial"
                    and node.value.args
                    and isinstance(node.value.args[0], ast.Name)
                ):
                    partials[node.targets[0].id] = node.value.args[0].id
            for node in ast.walk(fn):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"
                ):
                    kernel = "<unknown>"
                    if node.args:
                        arg = node.args[0]
                        if isinstance(arg, ast.Name):
                            kernel = partials.get(arg.id, arg.id)
                        elif (
                            isinstance(arg, ast.Call)
                            and _callee_name(arg.func) == "partial"
                            and arg.args
                            and isinstance(arg.args[0], ast.Name)
                        ):
                            kernel = arg.args[0].id
                    sites.append(
                        PallasSite(
                            relpath=f"kernels/{path.name}",
                            line=node.lineno,
                            wrapper=fn.name,
                            kernel_name=kernel,
                        )
                    )
    return sites


def _callee_name(func: ast.expr) -> str:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return ""


# ---------------------------------------------------------------------------
# contract capture (pallas_call interception)
# ---------------------------------------------------------------------------


@dataclass
class KernelContract:
    """The reconstructed contract of one pallas_call at one config."""

    kernel_name: str
    grid: tuple[int, ...]
    num_scalar_prefetch: int
    in_specs: tuple  # of pl.BlockSpec (block_shape + index_map introspected)
    out_specs: tuple
    scratch: tuple[tuple[tuple[int, ...], str], ...]  # (shape, dtype)
    out_shape: tuple[tuple[tuple[int, ...], str], ...]
    prefetch: tuple[np.ndarray, ...]  # concrete scalar-prefetch operands
    operands: tuple[tuple[tuple[int, ...], str], ...]  # tensor (shape, dtype)


class _Captured(Exception):
    pass


class _capture_pallas:
    """Context manager replacing ``pallas.pallas_call`` with a recorder:
    the returned callable snapshots the full contract at call time and
    raises, so no kernel is ever lowered or executed."""

    def __init__(self) -> None:
        self.contracts: list[KernelContract] = []

    def __enter__(self) -> "_capture_pallas":
        from jax.experimental import pallas as pl_mod

        self._mod = pl_mod
        self._real = pl_mod.pallas_call
        contracts = self.contracts

        def recorder(kernel, *, grid_spec=None, out_shape=None, **_kw):
            def runner(*args):
                gs = grid_spec
                if gs is None:
                    # plain-grid pallas_call (grid=/in_specs=/out_specs=
                    # kwargs, no scalar prefetch or scratch) — the delta
                    # kernel's shape
                    from types import SimpleNamespace

                    gs = SimpleNamespace(
                        grid=tuple(_kw.get("grid", ())),
                        in_specs=tuple(_kw.get("in_specs", ())),
                        out_specs=tuple(_kw.get("out_specs", ())),
                        scratch_shapes=tuple(_kw.get("scratch_shapes", ())),
                        num_scalar_prefetch=0,
                    )
                nsp = int(getattr(gs, "num_scalar_prefetch", 0))
                kname = getattr(
                    getattr(kernel, "func", kernel), "__name__", str(kernel)
                )
                oshape = (
                    list(out_shape)
                    if isinstance(out_shape, (list, tuple))
                    else [out_shape]
                )
                out_specs = gs.out_specs
                if not isinstance(out_specs, (list, tuple)):
                    out_specs = (out_specs,)
                contracts.append(
                    KernelContract(
                        kernel_name=kname,
                        grid=tuple(int(dim) for dim in gs.grid),
                        num_scalar_prefetch=nsp,
                        in_specs=tuple(gs.in_specs),
                        out_specs=tuple(out_specs),
                        scratch=tuple(
                            (tuple(s.shape), np.dtype(s.dtype).name)
                            for s in gs.scratch_shapes
                        ),
                        out_shape=tuple(
                            (tuple(o.shape), np.dtype(o.dtype).name)
                            for o in oshape
                        ),
                        prefetch=tuple(np.asarray(a) for a in args[:nsp]),
                        operands=tuple(
                            (tuple(a.shape), np.dtype(a.dtype).name)
                            for a in args[nsp:]
                        ),
                    )
                )
                raise _Captured(kname)

            return runner

        pl_mod.pallas_call = recorder
        return self

    def __exit__(self, *exc) -> None:
        self._mod.pallas_call = self._real


# ---------------------------------------------------------------------------
# audit specs + capture drivers
# ---------------------------------------------------------------------------


@dataclass(frozen=True, eq=False)
class AuditSpec:
    """One golden-corpus configuration to capture contracts at."""

    name: str
    q_ranges: np.ndarray
    k_ranges: np.ndarray
    d_lo: np.ndarray
    d_hi: np.ndarray
    sq: int
    sk: int
    hq: int
    hk: int
    blocks: tuple[int, int]
    d: int = 128
    dv: int = 128
    dtype: str = "bfloat16"
    dq_blocks: tuple[int, int] | None = None
    dkv_blocks: tuple[int, int] | None = None
    emit_ml: bool = False


def capture_ffa_contracts(spec: AuditSpec) -> list[KernelContract]:
    """Drive every FFA wrapper applicable at ``spec`` under capture.

    Applicability mirrors the runtime dispatch predicates in
    ``kernels/ffa.py`` minus their env flags (the audit proves every
    kernel a flag COULD route to), so a config the packed guards refuse
    is audited on the unpacked path only — exactly like the runtime.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels import ffa
    from ..kernels.ffa_plan import get_ffa_plan

    bq, bk = spec.blocks
    plan = get_ffa_plan(
        spec.q_ranges, spec.k_ranges, spec.d_lo, spec.d_hi,
        spec.sq, spec.sk, bq, bk,
    )
    sqp = plan.num_q_tiles * bq
    skp = plan.num_k_tiles * bk
    g = spec.hq // spec.hk
    itemsize = jnp.dtype(spec.dtype).itemsize

    arrays = ffa.plan_arrays(plan)
    dq_triple, dkv_triple = arrays[0:3], arrays[3:6]
    overrides: dict = {}
    if spec.dq_blocks:
        plan_dq = get_ffa_plan(
            spec.q_ranges, spec.k_ranges, spec.d_lo, spec.d_hi,
            spec.sq, spec.sk, *spec.dq_blocks,
        )
        dq_triple = ffa.plan_arrays(plan_dq)[0:3]
        overrides.update(
            block_q_dq=spec.dq_blocks[0], block_k_dq=spec.dq_blocks[1],
            num_work_dq=plan_dq.num_work,
        )
    if spec.dkv_blocks:
        plan_dkv = get_ffa_plan(
            spec.q_ranges, spec.k_ranges, spec.d_lo, spec.d_hi,
            spec.sq, spec.sk, *spec.dkv_blocks,
        )
        dkv_triple = ffa.plan_arrays(plan_dkv)[3:6]
        overrides.update(
            block_q_dkv=spec.dkv_blocks[0], block_k_dkv=spec.dkv_blocks[1],
            num_work_dkv=plan_dkv.num_work_t,
            min_revisit_distance=plan_dkv.min_revisit_distance,
        )
    overrides.setdefault("min_revisit_distance", plan.min_revisit_distance)

    params = ffa.FFAParams(
        num_work=plan.num_work,
        num_work_t=plan.num_work_t,
        num_q_tiles=plan.num_q_tiles,
        num_k_tiles=plan.num_k_tiles,
        block_q=bq,
        block_k=bk,
        softmax_scale=float(spec.d) ** -0.5,
        softcap=0.0,
        group=g,
        interpret=True,
        emit_max_logits=spec.emit_ml,
        **overrides,
    )
    dtype = jnp.dtype(spec.dtype)
    q_t = jnp.zeros((spec.hq, sqp, spec.d), dtype)
    k_t = jnp.zeros((spec.hk, skp, spec.d), dtype)
    v_t = jnp.zeros((spec.hk, skp, spec.dv), dtype)
    do_t = jnp.zeros((spec.hq, sqp, spec.dv), dtype)
    out_t = jnp.zeros((spec.hq, sqp, spec.dv), dtype)
    lse_t = jnp.zeros((spec.hq, sqp), jnp.float32)
    delta_t = jnp.zeros((spec.hq, sqp), jnp.float32)

    def pack_ok(kind: str, kbq: int, kbk: int) -> bool:
        return sqp % kbq == 0 and ffa.gqa_pack_fits(
            kind, g, kbq, kbk, spec.d, spec.dv, itemsize)

    def fused_ok(packed_flag: bool) -> bool:
        # mirrors ffa.fused_bwd_feasible: the runtime never routes an
        # over-budget config to the fused kernel, so the audit doesn't
        # drive one either
        kbq, kbk = params.dkv_blocks()
        if packed_flag and (g == 1 or sqp % kbq != 0):
            return False
        return (
            ffa_kernel_residency(
                "fused", kbq, kbk, spec.d, head_dim_v=spec.dv,
                dtype_bytes=itemsize, group=g, packed=packed_flag,
            )
            <= VMEM_ALLOWED_BYTES
        )

    runs: list[tuple] = [
        (ffa._ffa_fwd_pallas, (params, *arrays[0:3], q_t, k_t, v_t)),
        (ffa._ffa_bwd_dq_pallas,
         (params, *dq_triple, q_t, k_t, v_t, do_t, lse_t, delta_t)),
        (ffa._ffa_bwd_dkv_pallas,
         (params, *dkv_triple, q_t, k_t, v_t, do_t, lse_t, delta_t)),
        (ffa._ffa_delta_pallas, (out_t, do_t, bq, True)),
    ]
    if fused_ok(False):
        runs.append(
            (ffa._ffa_bwd_fused_pallas,
             (params, *dkv_triple, q_t, k_t, v_t, do_t, lse_t, delta_t))
        )
    if fused_ok(True):
        runs.append(
            (ffa._ffa_bwd_fused_pallas_gqa,
             (params, *dkv_triple, q_t, k_t, v_t, do_t, lse_t, delta_t))
        )
    if g > 1 and not spec.emit_ml and pack_ok("fwd", bq, bk):
        runs.append(
            (ffa._ffa_fwd_pallas_gqa, (params, *arrays[0:3], q_t, k_t, v_t))
        )
    if pack_ok("dq", *params.dq_blocks()):
        runs.append(
            (ffa._ffa_bwd_dq_pallas_gqa,
             (params, *dq_triple, q_t, k_t, v_t, do_t, lse_t, delta_t))
        )
    if pack_ok("dkv", *params.dkv_blocks()):
        runs.append(
            (ffa._ffa_bwd_dkv_pallas_gqa,
             (params, *dkv_triple, q_t, k_t, v_t, do_t, lse_t, delta_t))
        )

    contracts: list[KernelContract] = []
    with jax.default_device(jax.devices("cpu")[0]):
        for fn, args in runs:
            cap = _capture_pallas()
            with cap:
                try:
                    fn(*args)
                except _Captured:
                    pass
            contracts.extend(cap.contracts)
    return contracts


@dataclass(frozen=True, eq=False)
class DecodeAuditSpec:
    """One paged-decode corpus configuration (kernels/paged_decode.py).
    ``variant`` picks the wrapper driven: "base" (one row per slot),
    "spec" (``spec_k`` draft rows per slot, the speculative-verify
    kernel) or "int8" (quantized pages + per-page scale prefetch)."""

    name: str
    max_seqs: int = 4
    pages_per_seq: int = 8
    num_pages: int = 32
    page_size: int = 128
    hq: int = 4
    hk: int = 2
    d: int = 128
    dv: int = 128
    dtype: str = "bfloat16"
    lengths: tuple[int, ...] | None = None
    variant: str = "base"
    spec_k: int = 2


def decode_corpus() -> list[DecodeAuditSpec]:
    """Configs the decode kernels are captured at: the serving default, a
    wide-page fp32 variant, a ragged batch with dead slots + partially
    allocated page-table rows (-1 entries exercise the clamp index map),
    plus spec-verify (multi-row q tiles, both group widths) and int8
    (scale-prefetch index maps, fp32 compute dtype — the engine's) riders."""
    return [
        DecodeAuditSpec(name="decode/bfloat16/g2/ps128"),
        DecodeAuditSpec(
            name="decode/float32/g1/ps256", dtype="float32",
            hq=2, page_size=256, num_pages=16, pages_per_seq=4,
        ),
        DecodeAuditSpec(
            name="decode/bfloat16/g4/ragged", hq=8,
            lengths=(5, 0, 259, 128),
        ),
        DecodeAuditSpec(
            name="decode_spec/bfloat16/g2/k2/ps128", variant="spec",
        ),
        DecodeAuditSpec(
            name="decode_spec/float32/g4/k4/ragged", variant="spec",
            dtype="float32", hq=8, spec_k=4, lengths=(5, 0, 259, 128),
        ),
        DecodeAuditSpec(
            name="decode_int8/float32/g2/ps128", variant="int8",
            dtype="float32",
        ),
        DecodeAuditSpec(
            name="decode_int8/float32/g1/ps256", variant="int8",
            dtype="float32", hq=2, page_size=256, num_pages=16,
            pages_per_seq=4,
        ),
    ]


def capture_decode_contracts(spec: DecodeAuditSpec) -> list[KernelContract]:
    """Drive the paged-decode wrapper under capture at ``spec``: a cache
    whose page table is allocated exactly as the serving allocator would
    (pages in order per slot, -1 beyond each slot's allocation)."""
    import jax
    import jax.numpy as jnp

    from ..kernels import paged_decode
    from ..kernels.paged_kv import PagedKVCache

    ps = spec.page_size
    lengths = spec.lengths
    if lengths is None:
        lengths = tuple(
            min((i + 1) * ps, spec.pages_per_seq * ps)
            for i in range(spec.max_seqs)
        )
    table = np.full((spec.max_seqs, spec.pages_per_seq), -1, np.int32)
    nxt = 0
    for s, ln in enumerate(lengths):
        for j in range(-(-ln // ps)):
            table[s, j] = nxt % spec.num_pages
            nxt += 1
    dtype = jnp.dtype(spec.dtype)
    kv_dtype = jnp.int8 if spec.variant == "int8" else dtype
    scales = (
        jnp.zeros((spec.num_pages, spec.hk), jnp.float32)
        if spec.variant == "int8"
        else None
    )
    cache = PagedKVCache(
        k_pages=jnp.zeros(
            (spec.num_pages, ps, spec.hk, spec.d), kv_dtype
        ),
        v_pages=jnp.zeros(
            (spec.num_pages, ps, spec.hk, spec.dv), kv_dtype
        ),
        page_table=jnp.asarray(table),
        lengths=jnp.asarray(np.asarray(lengths, np.int32)),
        k_scales=scales,
        v_scales=scales,
    )
    if spec.variant == "spec":
        q = jnp.zeros((spec.max_seqs, spec.spec_k, spec.hq, spec.d), dtype)
        drive = lambda: paged_decode.paged_decode_attn_spec(q, cache)  # noqa: E731
    elif spec.variant == "int8":
        q = jnp.zeros((spec.max_seqs, spec.hq, spec.d), dtype)
        drive = lambda: paged_decode.paged_decode_attn_int8(q, cache)  # noqa: E731
    else:
        q = jnp.zeros((spec.max_seqs, spec.hq, spec.d), dtype)
        drive = lambda: paged_decode.paged_decode_attn(q, cache)  # noqa: E731
    cap = _capture_pallas()
    with jax.default_device(jax.devices("cpu")[0]):
        with cap:
            try:
                drive()
            except _Captured:
                pass
    return cap.contracts


@dataclass(frozen=True, eq=False)
class BlockSparseAuditSpec:
    """One block-sparse NSA-slc corpus config (kernels/block_sparse.py)."""

    name: str
    seq: int = 512
    hq: int = 4
    hk: int = 2
    d: int = 128
    dv: int = 128
    block_len: int = 64
    d_stride: int = 32
    block_size_q: int = 16
    top_k: int = 2
    dtype: str = "bfloat16"


def bsp_corpus() -> list[BlockSparseAuditSpec]:
    """Configs the block-sparse kernels are captured at: the NSA default
    (overlapping stride-32 blocks, GQA g=2), a non-overlapping fp32 g=1
    variant, and a wider-group bf16 config whose deterministic table picks
    adjacent blocks (maximal chunk duplication across the revisit axis)."""
    return [
        BlockSparseAuditSpec(name="bsp/bfloat16/g2/overlap"),
        BlockSparseAuditSpec(
            name="bsp/float32/g1/aligned", dtype="float32", hq=2,
            block_len=64, d_stride=64, top_k=3,
        ),
        BlockSparseAuditSpec(
            name="bsp/bfloat16/g4/adjacent", hq=8, seq=256, top_k=4,
        ),
    ]


def capture_bsp_contracts(spec: BlockSparseAuditSpec) -> list[KernelContract]:
    """Drive BOTH block-sparse wrappers (fwd + fused bwd) under capture at
    ``spec`` with a deterministic adjacent-block index table — the shape the
    NSA top-k emits, including overlapping picks when d_stride < block_len."""
    import jax
    import jax.numpy as jnp

    from ..kernels import block_sparse

    S, ds = spec.seq, spec.d_stride
    n_blocks = (S - spec.block_len) // ds + 1
    n_qb = S // spec.block_size_q
    n_chunks = S // ds
    alpha = spec.block_len // ds
    g = spec.hq // spec.hk
    r = spec.block_size_q * g
    dtype = jnp.dtype(spec.dtype)

    # adjacent distinct block ids per (head, q-block), wrapped in range
    idx = (
        np.arange(spec.top_k)[None, None, :]
        + np.arange(n_qb)[None, :, None]
        + np.arange(spec.hk)[:, None, None]
    ) % n_blocks
    starts = np.arange(n_blocks, dtype=np.int32) * ds
    ctbl = jnp.asarray(
        ((starts // ds)[idx][..., None] + np.arange(alpha))
        .reshape(spec.hk, n_qb, -1),
        jnp.int32,
    )
    C = spec.top_k * alpha

    q_r = jnp.zeros((spec.hk, n_qb, r, spec.d), dtype)
    k_c = jnp.zeros((n_chunks, ds, spec.hk, spec.d), dtype)
    v_c = jnp.zeros((n_chunks, ds, spec.hk, spec.dv), dtype)
    do_r = jnp.zeros((spec.hk, n_qb, r, spec.dv), dtype)
    lse_r = jnp.zeros((spec.hk, n_qb, r, 128), jnp.float32)
    delta_r = jnp.zeros((spec.hk, n_qb, r, 128), jnp.float32)
    scale = float(spec.d) ** -0.5

    contracts: list[KernelContract] = []
    with jax.default_device(jax.devices("cpu")[0]):
        for drive in (
            lambda: block_sparse._bsp_fwd_pallas(
                ctbl, q_r, k_c, v_c, scale, True
            ),
            lambda: block_sparse._bsp_bwd_pallas(
                ctbl, q_r, k_c, v_c, do_r, lse_r, delta_r, scale, True
            ),
        ):
            cap = _capture_pallas()
            with cap:
                try:
                    drive()
                except _Captured:
                    pass
            contracts.extend(cap.contracts)
    assert all(c.grid == (spec.hk, n_qb, C) for c in contracts)
    return contracts


def capture_ssd_contracts(
    tokens: int = 512, heads: int = 64, p: int = 64, groups: int = 8,
    n: int = 128,
) -> list[KernelContract]:
    """Drive both scan wrappers of ``kernels/ssd.py`` (the forward that
    saves its states, and the backward) under capture, at the head layout
    of the benchmark's hybrid cell."""
    import jax
    import jax.numpy as jnp

    from ..kernels import ssd

    dims = (heads // groups, p, n, groups)
    x2 = jnp.zeros((tokens, heads * p), jnp.bfloat16)
    bc = jnp.zeros((tokens, groups * n), jnp.bfloat16)
    per_head = jnp.zeros((heads, tokens), jnp.float32)
    sr = jnp.zeros((ssd.SEG_ROWS, tokens), jnp.float32)
    hin = jnp.zeros(
        (tokens // ssd.CHUNK, groups, n, heads // groups * p), jnp.float32)
    res = (x2, bc, bc, per_head, per_head, sr, hin)
    contracts: list[KernelContract] = []
    with jax.default_device(jax.devices("cpu")[0]):
        for drive in (
            lambda: ssd._fwd_call(
                x2, bc, bc, per_head, per_head, sr, dims, save=True),
            lambda: ssd._ssd_core_bwd(dims, res, x2),
        ):
            cap = _capture_pallas()
            with cap:
                try:
                    drive()
                except _Captured:
                    pass
            contracts.extend(cap.contracts)
    return contracts


def check_scan_contract(
    report: VerifyReport, contract: KernelContract, site: str
) -> None:
    """K1, K3 and K4 on a captured scan contract. K1 without a model of
    the body's intermediates (``mem_budget.ffa_kernel_residency`` knows
    attention tiles): the declared blocks, double-buffered, and the scratch
    must leave half of the allowed VMEM to them; the chip's compiler has the
    last word (``tests/test_models/test_step_schedule.py``)."""
    report.mark_run("K1")
    declared = _declared_bytes(contract)
    if 2 * declared > VMEM_ALLOWED_BYTES:
        report.add(
            "K1", ERROR, site,
            f"VMEM budget: declared blocks and scratch take {declared} "
            f"bytes/step, over half of the allowed {VMEM_ALLOWED_BYTES}",
        )
    check_k3_bounds(report, contract, site)
    check_k4_dtypes(report, contract, site)


def capture_grouped_contracts(
    tokens: int = 8192, top_k: int = 6, experts: int = 128, held: int = 32,
    dim: int = 2688, ffn: int = 1856,
) -> list[KernelContract]:
    """Drive the grouped matmul's two wrappers (``kernels/grouped_matmul.py``)
    under capture at the shapes of the benchmark's hybrid cell: the up
    product (float32 out; its weight is kept ``K``-minor, so the block is
    read transposed), the down product (rounded once), their two ``d rows``
    (the other reading of each weight) and ``dW`` (both are ``[held, ffn,
    dim]`` as stored); every held expert given its expected rows, the rest
    of the buffer past the groups."""
    import jax
    import jax.numpy as jnp

    from ..kernels import grouped_matmul, tile_policy

    m, rows_a_group = tokens * top_k, tokens * top_k // experts
    tile = tile_policy.grouped_row_tile(rows_a_group)
    sizes = jnp.full((held,), rows_a_group, jnp.int32)
    rows = jnp.zeros((m, dim), jnp.bfloat16)
    act = jnp.zeros((m, ffn), jnp.bfloat16)
    w_down = jnp.zeros((held, ffn, dim), jnp.bfloat16)
    contracts: list[KernelContract] = []
    with jax.default_device(jax.devices("cpu")[0]):
        w_up, k_minor = grouped_matmul._stored(
            jnp.zeros((held, dim, ffn), jnp.bfloat16))
        for drive in (
            lambda: grouped_matmul._product_call(
                rows, w_up, sizes, tile, jnp.float32, k_minor),
            lambda: grouped_matmul._product_call(
                act, w_down, sizes, tile, jnp.bfloat16, False),
            lambda: grouped_matmul._product_call(
                act, w_up, sizes, tile, jnp.bfloat16, not k_minor),
            lambda: grouped_matmul._product_call(
                rows, w_down, sizes, tile, jnp.bfloat16, True),
            lambda: grouped_matmul._dw_call(
                act, rows, sizes, tile, jnp.bfloat16),
        ):
            cap = _capture_pallas()
            with cap:
                try:
                    drive()
                except _Captured:
                    pass
            contracts.extend(cap.contracts)
    return contracts


def grouped_residency(contract: KernelContract) -> int:
    """Bytes of VMEM a grouped-matmul step holds: the declared blocks,
    double-buffered, the scratch, and the one intermediate of the body, the
    float32 product of the MXU at the size of the output block (the row
    tile by the column block, or ``dW``'s block before it is added in)."""
    product = _block_bytes(contract.out_specs[0].block_shape, "float32")
    return _declared_bytes(contract) + product


def check_grouped_contract(
    report: VerifyReport, contract: KernelContract, site: str
) -> None:
    """K1 and K4 on a captured grouped-matmul contract. K3's block bounds
    are not asked: a column block may end past a width that is no multiple
    of it (1856 in blocks of 640), which Pallas clips; the visit tables are
    held to a count by hand in ``tests/test_attn/test_grouped_matmul.py``."""
    report.mark_run("K1")
    total = grouped_residency(contract)
    if total > VMEM_ALLOWED_BYTES:
        report.add(
            "K1", ERROR, site,
            f"VMEM budget: {total} bytes/step (declared blocks, scratch "
            f"and the float32 product) exceeds the allowed "
            f"{VMEM_ALLOWED_BYTES}",
        )
    check_k4_dtypes(report, contract, site)


# ---------------------------------------------------------------------------
# contract geometry helpers
# ---------------------------------------------------------------------------


def _contract_shape_info(contract: KernelContract) -> dict:
    """(kind, packed, g, bq, bk, d, dv, itemsize, emit_ml) derived from the
    captured blocks — no reliance on the driver's inputs, so the checks
    also apply to synthetic/mutated contracts in tests."""
    name = contract.kernel_name
    packed = name.endswith("_gqa")
    if "delta" in name:
        # stateless map kernel: in_specs are (o, do), both (1, bq, dv)
        o_block = contract.in_specs[0].block_shape
        return dict(
            kind="delta", packed=False, g=1,
            bq=int(o_block[1]), bk=0,
            d=int(o_block[2]), dv=int(o_block[2]),
            itemsize=np.dtype(contract.operands[0][1]).itemsize,
            emit_ml=False,
        )
    if "decode" in name:
        # paged-decode kernels: q block (1, 1, rows, d), k/v blocks
        # (1, page_size, 1, d|dv); bq = q-tile rows (GQA group rows, or
        # spec_k * group rows for the verify variant), bk = page size.
        # int8/spec substrings dispatch to their own residency kinds and
        # MUST be tested before the generic branch — their names also
        # contain "decode". itemsize is always q's dtype; the int8 kind
        # bakes the 1-byte k/v payload + f32 scale blocks into its formula.
        q_block = contract.in_specs[0].block_shape
        k_block = contract.in_specs[1].block_shape
        v_block = contract.in_specs[2].block_shape
        kind = (
            "decode_int8" if "int8" in name
            else "decode_spec" if "spec" in name
            else "decode"
        )
        return dict(
            kind=kind, packed=False, g=1,
            bq=int(q_block[2]), bk=int(k_block[1]),
            d=int(q_block[3]), dv=int(v_block[3]),
            itemsize=np.dtype(contract.operands[0][1]).itemsize,
            emit_ml=False,
        )
    if "bsp" in name:
        # block-sparse kernels (kernels/block_sparse.py): q block
        # (1, 1, r, d) with r = block_size_q * group rows, k/v blocks
        # (1, d_stride, 1, d|dv); bq = r, bk = chunk rows. Checked BEFORE
        # the generic branch — "_bsp_fwd_kernel" also contains "fwd".
        q_block = contract.in_specs[0].block_shape
        k_block = contract.in_specs[1].block_shape
        v_block = contract.in_specs[2].block_shape
        return dict(
            kind="bsp_bwd" if "bwd" in name else "bsp_fwd",
            packed=False, g=1,
            bq=int(q_block[2]), bk=int(k_block[1]),
            d=int(q_block[3]), dv=int(v_block[3]),
            itemsize=np.dtype(contract.operands[0][1]).itemsize,
            emit_ml=False,
        )
    kind = (
        "fused" if "fused" in name
        else "fwd" if "fwd" in name
        else "dq" if "dq" in name
        else "dkv"
    )
    q_block = contract.in_specs[0].block_shape
    k_block = contract.in_specs[1].block_shape
    v_block = contract.in_specs[2].block_shape
    if packed:
        g, bq, d = int(q_block[1]), int(q_block[2]), int(q_block[3])
    else:
        g, bq, d = 1, int(q_block[1]), int(q_block[2])
    bk = int(k_block[1])
    dv = int(v_block[2])
    itemsize = np.dtype(contract.operands[0][1]).itemsize
    emit_ml = kind == "fwd" and not packed and len(contract.out_shape) == 3
    return dict(
        kind=kind, packed=packed, g=g, bq=bq, bk=bk, d=d, dv=dv,
        itemsize=itemsize, emit_ml=emit_ml,
    )


def _block_bytes(block_shape, dtype_name: str) -> int:
    n = 1
    for dim in block_shape:
        if dim is not None:
            n *= int(dim)
    return n * np.dtype(dtype_name).itemsize


def _declared_bytes(contract: KernelContract) -> int:
    """Exact declared residency from the captured contract: in/out blocks
    double-buffered + scratch. Scratch is counted at 4 bytes/elem by
    decree — its DTYPE is K4's rule, so a bf16-scratch mutation fires K4
    alone, not K1 as a side effect."""
    total = 0
    for spec, (_, dtype_name) in zip(
        contract.in_specs, contract.operands
    ):
        total += 2 * _block_bytes(spec.block_shape, dtype_name)
    for spec, (_, dtype_name) in zip(contract.out_specs, contract.out_shape):
        total += 2 * _block_bytes(spec.block_shape, dtype_name)
    for shape, _dtype in contract.scratch:
        total += int(np.prod(shape)) * 4
    return total


# ---------------------------------------------------------------------------
# K1 — VMEM budget
# ---------------------------------------------------------------------------


def check_k1_vmem(
    report: VerifyReport, contract: KernelContract, site: str
) -> None:
    report.mark_run("K1")
    info = _contract_shape_info(contract)
    declared = _declared_bytes(contract)
    model_declared = ffa_kernel_residency(
        info["kind"], info["bq"], info["bk"], info["d"],
        head_dim_v=info["dv"], dtype_bytes=info["itemsize"],
        group=info["g"], packed=info["packed"], emit_ml=info["emit_ml"],
        include_intermediates=False,
    )
    model_total = ffa_kernel_residency(
        info["kind"], info["bq"], info["bk"], info["d"],
        head_dim_v=info["dv"], dtype_bytes=info["itemsize"],
        group=info["g"], packed=info["packed"], emit_ml=info["emit_ml"],
    )
    intermediates = model_total - model_declared
    if declared != model_declared:
        report.add(
            "K1", ERROR, site,
            f"residency model drift: mem_budget.ffa_kernel_residency "
            f"predicts {model_declared} declared bytes but the captured "
            f"contract holds {declared} — the shared VMEM model no longer "
            f"matches the real kernel",
        )
    total = declared + intermediates
    if total > VMEM_ALLOWED_BYTES:
        report.add(
            "K1", ERROR, site,
            f"VMEM budget: {total} bytes/step (declared {declared} + "
            f"intermediates {intermediates}) exceeds the allowed "
            f"{VMEM_ALLOWED_BYTES} ({VMEM_LIMIT_BYTES} limit - "
            f"{VMEM_HEADROOM_BYTES} headroom)",
        )
    if not info["packed"] and info["kind"] in ("fwd", "dq", "dkv"):
        # cross-check against the vmem_check-guarded tile-policy model:
        # the policy filter and mem_budget must be the SAME arithmetic
        # (fused/delta have no tile_policy block filter — the fused path
        # reuses the dkv block space and gates on ffa_kernel_residency
        # directly, so there is no second model to diverge from)
        from ..kernels import tile_policy

        est_policy = (
            tile_policy._vmem_bytes(
                info["bq"], info["bk"], info["d"], info["dv"],
                info["itemsize"],
            )
            if info["kind"] == "fwd"
            else tile_policy._bwd_vmem_bytes(
                info["kind"], info["bq"], info["bk"], info["d"],
                info["dv"], info["itemsize"],
            )
        )
        est_budget = (
            fwd_vmem_bytes(
                info["bq"], info["bk"], info["d"], info["dv"],
                info["itemsize"],
            )
            if info["kind"] == "fwd"
            else bwd_vmem_bytes(
                info["kind"], info["bq"], info["bk"], info["d"],
                info["dv"], info["itemsize"],
            )
        )
        if est_policy != est_budget:
            report.add(
                "K1", ERROR, site,
                f"policy/runtime VMEM models diverge: tile_policy "
                f"estimates {est_policy} but mem_budget {est_budget} for "
                f"the same blocks — the vmem_check site no longer guards "
                f"the model this checker proves",
            )


def check_reachable_space(
    report: VerifyReport,
    sq: int,
    sk: int,
    d: int = 128,
    dv: int = 128,
    itemsizes: tuple[int, ...] = (2, 4),
    groups: tuple[int, ...] = (1, 2, 4, 8),
) -> dict:
    """Abstract K1 over the FULL reachable config space: every tiling
    ``tile_policy`` can emit for any pass must keep the UNPACKED kernel
    residency within budget (unpacked kernels launch unconditionally — no
    dispatch-time guard protects them), and the packed dispatch guards
    share :func:`ffa_kernel_residency`, so packed admission is safe by
    construction (asserted per captured contract in :func:`check_k1_vmem`).
    Returns sweep stats for the audit report."""
    from ..kernels import tile_policy

    report.mark_run("K1")
    checked = 0
    worst = (0, None)
    for kind in ("fwd", "dq", "dkv"):
        for itemsize in itemsizes:
            space = tile_policy.reachable_block_space(
                sq, sk, kind, d, dv, itemsize
            )
            for bq, bk in space:
                checked += 1
                total = ffa_kernel_residency(
                    kind, bq, bk, d, head_dim_v=dv, dtype_bytes=itemsize,
                    emit_ml=(kind == "fwd"),
                )
                if total > worst[0]:
                    worst = (total, (kind, bq, bk, itemsize))
                if total > VMEM_ALLOWED_BYTES:
                    report.add(
                        "K1", ERROR,
                        f"reachable_block_space(sq={sq}, sk={sk}, "
                        f"{kind}, itemsize={itemsize})",
                        f"policy-reachable tiling ({bq}, {bk}) puts the "
                        f"unpacked {kind} kernel at {total} bytes/step > "
                        f"allowed {VMEM_ALLOWED_BYTES}",
                    )
                # packed admission is the guard's decision; prove the
                # guard's model here so a guard bypass cannot hide
                for g in groups:
                    if g == 1:
                        continue
                    packed_total = ffa_kernel_residency(
                        kind, bq, bk, d, head_dim_v=dv,
                        dtype_bytes=itemsize, group=g, packed=True,
                    )
                    admitted = packed_total <= VMEM_ALLOWED_BYTES
                    if admitted and packed_total > VMEM_ALLOWED_BYTES:
                        report.add(  # pragma: no cover - tautology guard
                            "K1", ERROR, "packed dispatch guard",
                            f"guard admits ({kind}, g={g}, {bq}x{bk}) at "
                            f"{packed_total} bytes",
                        )
    return {
        "configs_checked": checked,
        "worst_bytes": worst[0],
        "worst_config": worst[1],
        "allowed_bytes": VMEM_ALLOWED_BYTES,
    }


# ---------------------------------------------------------------------------
# K3 — index-map bounds
# ---------------------------------------------------------------------------


def _grid_mesh(grid: tuple[int, ...]) -> list[np.ndarray]:
    axes = [np.arange(n, dtype=np.int64) for n in grid]
    return list(np.meshgrid(*axes, indexing="ij")) if axes else []


def _eval_index_map(spec, mesh, prefetch):
    out = spec.index_map(*mesh, *prefetch)
    if not isinstance(out, tuple):
        out = (out,)
    shape = mesh[0].shape if mesh else ()
    return [np.broadcast_to(np.asarray(o), shape) for o in out]


def check_k3_bounds(
    report: VerifyReport, contract: KernelContract, site: str
) -> None:
    report.mark_run("K3")
    mesh = _grid_mesh(contract.grid)
    pairs = [
        (f"in[{i}]", spec, shape)
        for i, (spec, (shape, _)) in enumerate(
            zip(contract.in_specs, contract.operands)
        )
    ] + [
        (f"out[{i}]", spec, shape)
        for i, (spec, (shape, _)) in enumerate(
            zip(contract.out_specs, contract.out_shape)
        )
    ]
    for label, spec, op_shape in pairs:
        block = spec.block_shape
        if len(block) != len(op_shape):
            report.add(
                "K3", ERROR, f"{site} {label}",
                f"block rank {len(block)} != operand rank {len(op_shape)}",
            )
            continue
        idx = _eval_index_map(spec, mesh, contract.prefetch)
        if len(idx) != len(block):
            report.add(
                "K3", ERROR, f"{site} {label}",
                f"index_map returns {len(idx)} indices for a rank-"
                f"{len(block)} block",
            )
            continue
        for axis, (bdim, dim) in enumerate(zip(block, op_shape)):
            ext = 1 if bdim is None else int(bdim)
            origin = idx[axis] * (1 if bdim is None else int(bdim))
            lo = int(origin.min()) if origin.size else 0
            hi = int(origin.max()) + ext if origin.size else ext
            if lo < 0 or hi > dim:
                report.add(
                    "K3", ERROR, f"{site} {label}",
                    f"axis {axis}: block [{lo}, {hi}) escapes operand "
                    f"dim {dim} (block {ext} x index range "
                    f"[{int(origin.min())}, {int(origin.max())}])",
                )


def check_k3_extents(
    report: VerifyReport, contract: KernelContract, site: str
) -> None:
    """K3, extent half: the EQ0..EK1 live-extent meta columns are prefetch
    state the clamp path uses to SKIP dot_general chunks, so a wrong row
    silently drops (or re-adds) attention mass instead of faulting. Prove
    every captured row equals the host-side recomputation from the 9-col
    band geometry (``ffa_plan._extend_meta_extents``) and sits inside the
    tile at the sublane/lane quanta the kernels chunk at."""
    if contract.num_scalar_prefetch < 3:
        return
    meta = np.asarray(contract.prefetch[2])
    if meta.ndim != 2 or meta.shape[1] < META_DIM:
        return  # pre-extent 9-col meta: nothing to prove
    report.mark_run("K3")
    info = _contract_shape_info(contract)
    bq, bk = info["bq"], info["bk"]
    work_qt = np.asarray(contract.prefetch[0])
    work_kt = np.asarray(contract.prefetch[1])
    ext = meta[:, EQ0 : EK1 + 1].astype(np.int64)
    want = _extend_meta_extents(
        meta[:, :EQ0].astype(np.int32), work_qt, work_kt, bq, bk
    )[:, EQ0 : EK1 + 1].astype(np.int64)
    bad = np.nonzero((ext != want).any(axis=1))[0]
    for w in bad[:8]:
        report.add(
            "K3", ERROR, f"{site} meta[{int(w)}]",
            f"extent columns {ext[w].tolist()} != host recomputation "
            f"{want[w].tolist()} from the band geometry — the clamp "
            f"path would skip live chunks or execute dead ones",
        )
    if len(bad) > 8:
        report.add(
            "K3", ERROR, site,
            f"... and {len(bad) - 8} more extent rows disagree",
        )
    eq0, eq1, ek0, ek1 = ext[:, 0], ext[:, 1], ext[:, 2], ext[:, 3]
    oob = (
        (eq0 < 0) | (eq1 > bq) | (eq0 > eq1)
        | (ek0 < 0) | (ek1 > bk) | (ek0 > ek1)
    )
    for w in np.nonzero(oob)[0][:8]:
        report.add(
            "K3", ERROR, f"{site} meta[{int(w)}]",
            f"extent {ext[w].tolist()} escapes tile ({bq}, {bk}) or is "
            f"inverted",
        )
    misaligned = (
        (eq0 % SUBLANE_QUANTUM != 0) | (eq1 % SUBLANE_QUANTUM != 0)
        | (ek0 % LANE_QUANTUM != 0) | (ek1 % LANE_QUANTUM != 0)
    )
    for w in np.nonzero(misaligned & ~oob)[0][:8]:
        report.add(
            "K3", ERROR, f"{site} meta[{int(w)}]",
            f"extent {ext[w].tolist()} not aligned to "
            f"({SUBLANE_QUANTUM}, {LANE_QUANTUM}) quanta — chunk "
            f"liveness tests would straddle a partially-live chunk",
        )


def padding_stats(
    contract: KernelContract, sq: int, sk: int
) -> dict:
    """Statically counted padded-tile work for the audit report (feeds
    roadmap item 3's block-skip dispatch): grid steps whose q or k tile
    sticks out past the true seqlen."""
    info = _contract_shape_info(contract)
    if contract.num_scalar_prefetch < 2:
        return {}
    work_qt = contract.prefetch[0].astype(np.int64)
    work_kt = contract.prefetch[1].astype(np.int64)
    q_pad = (work_qt + 1) * info["bq"] > sq
    k_pad = (work_kt + 1) * info["bk"] > sk
    steps = int(work_qt.size)
    return {
        "grid_steps": steps,
        "padded_q_steps": int(q_pad.sum()),
        "padded_k_steps": int(k_pad.sum()),
        "padded_steps": int((q_pad | k_pad).sum()),
        "padded_ratio": float((q_pad | k_pad).sum()) / steps if steps else 0.0,
    }


# ---------------------------------------------------------------------------
# K4 — dtype/precision contract (captured side)
# ---------------------------------------------------------------------------


def check_k4_dtypes(
    report: VerifyReport, contract: KernelContract, site: str,
    declared: dict | None = None,
) -> None:
    report.mark_run("K4")
    for i, (shape, dtype_name) in enumerate(contract.scratch):
        if dtype_name != "float32":
            report.add(
                "K4", ERROR, f"{site} scratch[{i}]",
                f"accumulator scratch {shape} is {dtype_name}, not "
                f"float32 — cross-step accumulation would truncate",
            )
    if declared is None:
        declared = _pallas_contracts().get(contract.kernel_name)
    if declared is None:
        return
    input_dtype = contract.operands[0][1] if contract.operands else None
    for i, want in enumerate(declared.get("out_dtypes", ())):
        if i >= len(contract.out_shape):
            break  # trailing optional output (ml) absent at this config
        got = contract.out_shape[i][1]
        if want == "f32" and got != "float32":
            report.add(
                "K4", ERROR, f"{site} out[{i}]",
                f"declared f32 output lowered as {got} — implicit "
                f"truncation before the final write",
            )
        elif want == "input" and input_dtype and got != input_dtype:
            report.add(
                "K4", ERROR, f"{site} out[{i}]",
                f"passthrough output dtype {got} != operand dtype "
                f"{input_dtype}",
            )
        elif want == "f32_or_input" and got not in ("float32", input_dtype):
            report.add(
                "K4", ERROR, f"{site} out[{i}]",
                f"output dtype {got} is neither the float32 accumulator "
                f"nor its one rounding to the operand dtype {input_dtype}",
            )


def _pallas_contracts() -> dict:
    from ..kernels.block_sparse import PALLAS_CONTRACTS as bsp_contracts
    from ..kernels.ffa import PALLAS_CONTRACTS as ffa_contracts
    from ..kernels.grouped_matmul import PALLAS_CONTRACTS as grouped_contracts
    from ..kernels.paged_decode import PALLAS_CONTRACTS as decode_contracts
    from ..kernels.ssd import PALLAS_CONTRACTS as ssd_contracts

    return {**ffa_contracts, **decode_contracts, **bsp_contracts,
            **ssd_contracts, **grouped_contracts}


def _contract_sources() -> list[tuple[str, str, dict]]:
    """(relpath, source, contracts) for every kernel module that declares
    PALLAS_CONTRACTS — the K2/K4 source-rule sweep iterates these."""
    from ..kernels.block_sparse import PALLAS_CONTRACTS as bsp_contracts
    from ..kernels.ffa import PALLAS_CONTRACTS as ffa_contracts
    from ..kernels.grouped_matmul import PALLAS_CONTRACTS as grouped_contracts
    from ..kernels.paged_decode import PALLAS_CONTRACTS as decode_contracts
    from ..kernels.ssd import PALLAS_CONTRACTS as ssd_contracts

    kdir = _kernels_dir()
    return [
        ("kernels/ffa.py", (kdir / "ffa.py").read_text(), ffa_contracts),
        (
            "kernels/paged_decode.py",
            (kdir / "paged_decode.py").read_text(),
            decode_contracts,
        ),
        (
            "kernels/block_sparse.py",
            (kdir / "block_sparse.py").read_text(),
            bsp_contracts,
        ),
        ("kernels/ssd.py", (kdir / "ssd.py").read_text(), ssd_contracts),
        (
            "kernels/grouped_matmul.py",
            (kdir / "grouped_matmul.py").read_text(),
            grouped_contracts,
        ),
    ]


def check_contract(
    report: VerifyReport, contract: KernelContract, site: str | None = None
) -> None:
    """K1 + K3 + K4 on one captured contract (K2/K5 are source/repo-level)."""
    site = site or contract.kernel_name
    check_k1_vmem(report, contract, site)
    check_k3_bounds(report, contract, site)
    check_k3_extents(report, contract, site)
    check_k4_dtypes(report, contract, site)


# ---------------------------------------------------------------------------
# K2 — accumulator discipline + K4 source rules (AST over kernel bodies)
# ---------------------------------------------------------------------------


def _guard_conds(expr: ast.expr) -> list[tuple[str, str]] | None:
    """Flatten a ``pl.when`` predicate into (name, rhs) equality pairs;
    None when the shape is unrecognized."""
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.BitAnd):
        left = _guard_conds(expr.left)
        right = _guard_conds(expr.right)
        if left is None or right is None:
            return None
        return left + right
    if (
        isinstance(expr, ast.Compare)
        and len(expr.ops) == 1
        and isinstance(expr.ops[0], ast.Eq)
        and isinstance(expr.left, ast.Name)
    ):
        return [(expr.left.id, ast.unparse(expr.comparators[0]))]
    if isinstance(expr, ast.Name):  # a boolean local, e.g. ``moved``
        return [(expr.id, "True")]
    return None


def _when_blocks(fn: ast.FunctionDef) -> list[tuple[list, ast.FunctionDef]]:
    blocks = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.FunctionDef) or node is fn:
            continue
        for dec in node.decorator_list:
            if (
                isinstance(dec, ast.Call)
                and isinstance(dec.func, ast.Attribute)
                and dec.func.attr == "when"
                and dec.args
            ):
                conds = _guard_conds(dec.args[0])
                if conds is not None:
                    blocks.append((conds, node))
    return blocks


def _subscript_stores(node: ast.AST, names: tuple[str, ...]) -> dict[str, list]:
    """name -> list of Assign/AugAssign nodes whose target subscripts it."""
    stores: dict[str, list] = {n: [] for n in names}
    for sub in ast.walk(node):
        targets = []
        if isinstance(sub, ast.Assign):
            targets = sub.targets
        elif isinstance(sub, ast.AugAssign):
            targets = [sub.target]
        for t in targets:
            if (
                isinstance(t, ast.Subscript)
                and isinstance(t.value, ast.Name)
                and t.value.id in stores
            ):
                stores[t.value.id].append(sub)
    return stores


def check_kernel_sources(
    report: VerifyReport,
    source: str | None = None,
    contracts: dict | None = None,
    relpath: str = "kernels/ffa.py",
) -> None:
    """K2 (+ the source half of K4) over the kernel bodies declared in
    ``PALLAS_CONTRACTS``. With no ``source``/``contracts`` the sweep covers
    every kernel module in :func:`_contract_sources`; tests pass mutated
    fixtures explicitly."""
    if source is None and contracts is None:
        for rel, src, decls in _contract_sources():
            _check_kernel_sources_one(report, src, decls, rel)
        return
    if contracts is None:
        contracts = _pallas_contracts()
    if source is None:
        source = (_kernels_dir() / "ffa.py").read_text()
    _check_kernel_sources_one(report, source, contracts, relpath)


def _check_kernel_sources_one(
    report: VerifyReport,
    source: str,
    contracts: dict,
    relpath: str,
) -> None:
    report.mark_run("K2")
    report.mark_run("K4")
    tree = ast.parse(source)
    fns = {
        node.name: node
        for node in ast.walk(tree)
        if isinstance(node, ast.FunctionDef)
    }
    for kname, decl in contracts.items():
        site = f"{relpath}:{kname}"
        fn = fns.get(kname)
        if fn is None:
            report.add(
                "K2", ERROR, site,
                "annotated kernel body not found in source — "
                "PALLAS_CONTRACTS out of date",
            )
            continue
        # K4 source half: every MXU contraction accumulates in f32
        # (runs for every contract, including stateless map kernels)
        for node in ast.walk(fn):
            if (
                isinstance(node, ast.Call)
                and _callee_name(node.func) == "dot_general"
            ):
                kw = {k.arg: k.value for k in node.keywords}
                pet = kw.get("preferred_element_type")
                if pet is None or not ast.unparse(pet).endswith("float32"):
                    report.add(
                        "K4", ERROR, f"{site}:{node.lineno}",
                        "dot_general without "
                        "preferred_element_type=jnp.float32 — MXU "
                        "accumulation falls back to the input dtype",
                    )

        init_guard = decl["init_guard"]
        flush_guard = decl["flush_guard"]
        group = decl.get("group_inner")
        # revisit: one dict or a list of dicts, one per revisit-accumulated
        # output. Each may override the guard-binding substrings
        # (init_binding / flush_binding, defaults QVF / QVL for the plan-
        # meta kernels) and may declare flush_guard=None for outputs whose
        # accumulated value is final as-is (host-side correction only)
        revisit = decl.get("revisit")
        revisits = (
            [revisit] if isinstance(revisit, dict) else list(revisit or [])
        )

        if init_guard is None and flush_guard is None:
            # stateless map kernel (e.g. the delta kernel): no cross-step
            # accumulator, so the only K2 obligation is that every
            # declared output is actually written
            for name in decl["outputs"]:
                if not _subscript_stores(fn, (name,))[name]:
                    report.add(
                        "K2", ERROR, site,
                        f"output '{name}' is never stored",
                    )
            continue

        # guard vars must be derived from the plan's IS_FIRST / IS_LAST
        # (and, for a revisit-accumulated output, QVF / QVL)
        bindings = {}
        for node in ast.walk(fn):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
            ):
                bindings[node.targets[0].id] = ast.unparse(node.value)
        # guard-binding provenance: plan-meta kernels bind from IS_FIRST /
        # IS_LAST columns; grid-axis kernels (the paged-decode page run)
        # declare their expected binding substrings explicitly
        guard_cols = [
            (init_guard, decl.get("init_binding", "IS_FIRST")),
            (flush_guard, decl.get("flush_binding", "IS_LAST")),
        ]
        for rv in revisits:
            guard_cols.append(
                (rv["init_guard"], rv.get("init_binding", "QVF"))
            )
            if rv.get("flush_guard") is not None:
                guard_cols.append(
                    (rv["flush_guard"], rv.get("flush_binding", "QVL"))
                )
        for var, col in guard_cols:
            if col not in bindings.get(var, ""):
                report.add(
                    "K2", ERROR, site,
                    f"guard variable '{var}' is not bound from the plan's "
                    f"{col} column",
                )

        blocks = _when_blocks(fn)
        init_blocks = [
            (conds, node) for conds, node in blocks
            if (init_guard, "1") in conds
        ]
        flush_blocks = [
            (conds, node) for conds, node in blocks
            if (flush_guard, "1") in conds
        ]

        if group:
            var, count = group["var"], group["count"]
            for conds, _node in init_blocks:
                if (var, "0") not in conds:
                    report.add(
                        "K2", ERROR, site,
                        f"init guard lacks the inner-revisit qualifier "
                        f"({var} == 0): the grid revisits this tile "
                        f"across '{var}', so a bare {init_guard} re-zeros "
                        f"a live accumulator",
                    )
            for conds, _node in flush_blocks:
                if (var, f"{count} - 1") not in conds:
                    report.add(
                        "K2", ERROR, site,
                        f"flush guard lacks the inner-revisit qualifier "
                        f"({var} == {count} - 1): the output would be "
                        f"written {count} times per tile run",
                    )

        # every scratch accumulator zero-initialized inside an init block
        scratch = tuple(decl["scratch"])
        initialized: set[str] = set()
        init_fns = {"zeros_like", "full_like", "zeros", "full"}
        for _conds, node in init_blocks:
            for name, assigns in _subscript_stores(node, scratch).items():
                for a in assigns:
                    val = getattr(a, "value", None)
                    if (
                        isinstance(a, ast.Assign)
                        and isinstance(val, ast.Call)
                        and _callee_name(val.func) in init_fns
                    ):
                        initialized.add(name)
        for name in scratch:
            if name not in initialized:
                report.add(
                    "K2", ERROR, site,
                    f"scratch accumulator '{name}' is never zero-"
                    f"initialized under the {init_guard} guard — first "
                    f"grid step reads stale VMEM",
                )

        # outputs: stored exactly once, only under the flush guard
        # (a revisit-accumulated output follows its own discipline below)
        revisit_outs = {rv["out"] for rv in revisits}
        outputs = tuple(
            n for n in decl["outputs"] if n not in revisit_outs
        )
        flush_assigns: dict[str, int] = {n: 0 for n in outputs}
        flush_nodes: set[int] = set()
        for _conds, node in flush_blocks:
            for name, assigns in _subscript_stores(node, outputs).items():
                flush_assigns[name] += len(assigns)
                flush_nodes.update(id(a) for a in assigns)
        all_assigns = _subscript_stores(fn, outputs)
        for name in outputs:
            stray = [
                a for a in all_assigns[name] if id(a) not in flush_nodes
            ]
            if stray:
                report.add(
                    "K2", ERROR, site,
                    f"output '{name}' is stored outside the {flush_guard} "
                    f"flush guard (line {stray[0].lineno}) — partial "
                    f"accumulation would be written",
                )
            if flush_assigns[name] == 0:
                report.add(
                    "K2", ERROR, site,
                    f"output '{name}' is never flushed under the "
                    f"{flush_guard} guard",
                )
            elif flush_assigns[name] > 1:
                report.add(
                    "K2", ERROR, site,
                    f"output '{name}' is flushed {flush_assigns[name]} "
                    f"times — the contract requires exactly one flush",
                )

        # revisit-accumulated outputs: the traversal revisits the same
        # output block across work items, so the kernel must (a) zero it
        # on the FIRST visit — on hardware the window's initial VMEM
        # content is undefined; interpret mode hides this — (b) when a
        # last-visit correction is declared (flush_guard not None), flush
        # exactly once on the LAST visit, and (c) only ever accumulate
        # (+=) in between, never overwrite. Where the visits are not
        # adjacent the chip writes the window back and does NOT read it
        # back: (d) a declared ``readback`` operand (the output's own
        # array, aliased and indexed like it) must be copied in on a later
        # visit — one plain store, under the first-visit guard's complement
        for rv in revisits:
            rout = rv["out"]
            rvf = rv["init_guard"]
            rvl = rv.get("flush_guard")
            rback = rv.get("readback")
            r_back_ids: set[int] = set()
            if rback is not None:
                for conds, node in blocks:
                    if (rvf, "0") not in conds:
                        continue
                    for a in _subscript_stores(node, (rout,))[rout]:
                        if isinstance(a, ast.Assign) and any(
                            isinstance(n, ast.Name) and n.id == rback
                            for n in ast.walk(a.value)
                        ):
                            r_back_ids.add(id(a))
                if len(r_back_ids) != 1:
                    report.add(
                        "K2", ERROR, site,
                        f"revisit-accumulated output '{rout}' is read back "
                        f"from '{rback}' {len(r_back_ids)} times under the "
                        f"{rvf} == 0 (later-visit) guard — the contract "
                        f"requires exactly one: the chip does not fetch an "
                        f"output window, so a later visit would accumulate "
                        f"on whatever tile the buffer held last",
                    )
            r_init_ids: set[int] = set()
            has_init = False
            for conds, node in blocks:
                if (rvf, "1") not in conds:
                    continue
                for a in _subscript_stores(node, (rout,))[rout]:
                    r_init_ids.add(id(a))
                    val = getattr(a, "value", None)
                    if (
                        isinstance(a, ast.Assign)
                        and isinstance(val, ast.Call)
                        and _callee_name(val.func) in init_fns
                    ):
                        has_init = True
            if not has_init:
                report.add(
                    "K2", ERROR, site,
                    f"revisit-accumulated output '{rout}' is never zero-"
                    f"initialized under the {rvf} (first-visit) guard — "
                    f"on hardware the output window's first-visit VMEM "
                    f"content is undefined, so accumulation starts from "
                    f"garbage",
                )
            r_flush_ids: set[int] = set()
            if rvl is not None:
                n_flush = 0
                for conds, node in blocks:
                    if (rvl, "1") not in conds:
                        continue
                    assigns = _subscript_stores(node, (rout,))[rout]
                    n_flush += len(assigns)
                    r_flush_ids.update(id(a) for a in assigns)
                if n_flush == 0:
                    report.add(
                        "K2", ERROR, site,
                        f"revisit-accumulated output '{rout}' is never "
                        f"flushed under the {rvl} (last-visit) guard",
                    )
                elif n_flush > 1:
                    report.add(
                        "K2", ERROR, site,
                        f"revisit-accumulated output '{rout}' is flushed "
                        f"{n_flush} times — the contract requires exactly "
                        f"one last-visit flush",
                    )
            for a in _subscript_stores(fn, (rout,))[rout]:
                if id(a) in r_init_ids | r_flush_ids | r_back_ids:
                    continue
                if not isinstance(a, ast.AugAssign):
                    report.add(
                        "K2", ERROR, site,
                        f"revisit-accumulated output '{rout}' is plainly "
                        f"assigned outside the {rvf}/{rvl} guards (line "
                        f"{a.lineno}) — a revisit would overwrite, not "
                        f"accumulate, earlier work items' contributions",
                    )


# ---------------------------------------------------------------------------
# K5 — cache-key soundness
# ---------------------------------------------------------------------------

_ENV_KEY_RE = "MAGI_ATTENTION_"


def _env_getter_keys(env_dir: Path) -> dict[str, set[str]]:
    """getter function name -> env keys it reads, from env/*.py ASTs."""
    getters: dict[str, set[str]] = {}
    for path in sorted(env_dir.glob("*.py")):
        tree = ast.parse(path.read_text())
        for fn in ast.walk(tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            keys = {
                node.value
                for node in ast.walk(fn)
                if isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and node.value.startswith(_ENV_KEY_RE)
            }
            if keys:
                getters.setdefault(fn.name, set()).update(keys)
    return getters


def consumed_env_keys(
    kernels_dir: Path | None = None, env_dir: Path | None = None
) -> dict[str, set[str]]:
    """env key -> the kernels/ files consuming it (directly via a MAGI_*
    literal or through an env/ getter call)."""
    kroot = Path(kernels_dir) if kernels_dir else _kernels_dir()
    eroot = Path(env_dir) if env_dir else kroot.parent / "env"
    getters = _env_getter_keys(eroot)
    consumed: dict[str, set[str]] = {}
    for path in sorted(kroot.glob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _callee_name(node.func)
            for key in getters.get(callee, ()):
                consumed.setdefault(key, set()).add(path.name)
            for arg in node.args[:1]:
                if (
                    isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                    and arg.value.startswith(_ENV_KEY_RE)
                ):
                    consumed.setdefault(arg.value, set()).add(path.name)
    return consumed


def check_env_keys(
    report: VerifyReport,
    consumed: dict[str, set[str]] | None = None,
    listed: tuple[str, ...] | None = None,
    allowlist: dict[str, str] | None = None,
) -> None:
    """K5: every env key that can change kernel lowering (= consumed under
    kernels/) must invalidate runtime caches via
    ENV_KEYS_AFFECTING_RUNTIME, unless allowlisted with a proof."""
    report.mark_run("K5")
    if consumed is None:
        consumed = consumed_env_keys()
    if listed is None:
        from ..env.general import ENV_KEYS_AFFECTING_RUNTIME

        listed = ENV_KEYS_AFFECTING_RUNTIME
    if allowlist is None:
        allowlist = K5_ALLOWLIST
    for key in sorted(consumed):
        if key in listed or key in allowlist:
            continue
        files = ", ".join(sorted(consumed[key]))
        report.add(
            "K5", ERROR, f"kernels/ ({files})",
            f"env key {key} changes kernel behavior but is missing from "
            f"ENV_KEYS_AFFECTING_RUNTIME — cached runtimes would be "
            f"shared across flag flips",
        )


# ---------------------------------------------------------------------------
# golden corpus + full audit
# ---------------------------------------------------------------------------

_SEQ = 1024


def _canonical_masks(seq: int = _SEQ) -> dict[str, tuple]:
    """Small self-contained mask set spanning the plan-shape classes the
    scripts/verify_plans.py corpus uses (dense, causal, varlen, sliding
    window, block-sparse). Returns name -> (qr, kr, d_lo, d_hi)."""
    from ..kernels.mask_utils import types_to_bands

    def bands(qr, kr, tm):
        qr = np.asarray(qr, dtype=np.int32)
        kr = np.asarray(kr, dtype=np.int32)
        tm = np.asarray(tm, dtype=np.int32)
        lo, hi = types_to_bands(qr, kr, tm)
        return qr, kr, lo, hi

    h = seq // 2
    quarter = seq // 4
    masks = {
        "full": bands([[0, seq]], [[0, seq]], [0]),
        "causal": bands([[0, seq]], [[0, seq]], [1]),
        "varlen_block_causal": bands(
            [[0, quarter], [quarter, h], [h, seq]],
            [[0, quarter], [quarter, h], [h, seq]],
            [1, 1, 1],
        ),
        "sliding_window": (
            np.asarray([[0, seq]], dtype=np.int32),
            np.asarray([[0, seq]], dtype=np.int32),
            np.asarray([-256], dtype=np.int32),
            np.asarray([0], dtype=np.int32),
        ),
        "block_sparse": bands(
            [[0, quarter], [h, h + quarter]],
            [[quarter, h], [0, quarter]],
            [0, 0],
        ),
    }
    return masks


def _fragmented_masks(seq: int = _SEQ) -> dict[str, tuple]:
    """Sparse masks whose tiles are mostly padding at the default blocks —
    the shapes the extent-clamp/mixed-dispatch rescue targets. Shared with
    the verify_plans and parity corpora (video-style windowed frames via
    utils/sparse_utils, plus a fine block-diagonal)."""
    from ..kernels.mask_utils import types_to_bands
    from ..utils.sparse_utils import block_mask_to_ranges, make_video_block_mask

    blk = 128
    frames = seq // blk
    bm = make_video_block_mask(frames, 1, window_frames=2)
    vq, vk, vt = block_mask_to_ranges(bm, blk, blk)
    vqr = np.asarray(vq.to_naive_ranges(), dtype=np.int32)
    vkr = np.asarray(vk.to_naive_ranges(), dtype=np.int32)
    vtm = np.asarray([t.to_int_type() for t in vt], dtype=np.int32)
    vlo, vhi = types_to_bands(vqr, vkr, vtm)

    n = seq // blk
    dqr = np.asarray([[i * blk, (i + 1) * blk] for i in range(n)], np.int32)
    dlo, dhi = types_to_bands(dqr, dqr, np.zeros(n, dtype=np.int32))
    return {
        "video_sparse": (vqr, vkr, vlo, vhi),
        "block_diag_sparse": (dqr, dqr.copy(), dlo, dhi),
    }


def _largest_reachable_blocks(seq: int, itemsize: int) -> tuple[int, int]:
    """Max-area tiling reachable for EVERY pass at this dtype — the fwd
    blocks serve dq/dkv whenever no override is active, so the audit's
    'largest' sample must sit in the intersection of the per-pass
    reachable spaces (e.g. (1024, 1024) fits the fwd budget at fp32 but
    busts the dkv kernel's VMEM, so the policy never emits it for dkv)."""
    from ..kernels import tile_policy

    spaces = [
        set(tile_policy.reachable_block_space(seq, seq, kind, 128, 128, itemsize))
        for kind in ("fwd", "dq", "dkv")
    ]
    common = set.intersection(*spaces)
    return max(common, key=lambda p: (p[0] * p[1], p))


def golden_corpus(seq: int = _SEQ) -> list[AuditSpec]:
    """mask kinds x block sizes x dtypes x GQA group — the sampled config
    corpus the audit captures real contracts at (the abstract
    :func:`check_reachable_space` sweep covers the rest of the space)."""
    specs: list[AuditSpec] = []
    masks = _canonical_masks(seq)
    for mask_name, (qr, kr, lo, hi) in masks.items():
        for dtype in ("bfloat16", "float32"):
            itemsize = 2 if dtype == "bfloat16" else 4
            block_choices = dict.fromkeys(
                ((256, 512), (128, 128),
                 _largest_reachable_blocks(seq, itemsize))
            )
            for g in (1, 2, 4):
                hk = 2
                hq = hk * g
                for blocks in block_choices:
                    specs.append(
                        AuditSpec(
                            name=(
                                f"{mask_name}/{dtype}/g{g}/"
                                f"b{blocks[0]}x{blocks[1]}"
                            ),
                            q_ranges=qr, k_ranges=kr, d_lo=lo, d_hi=hi,
                            sq=seq, sk=seq, hq=hq, hk=hk, blocks=blocks,
                            dtype=dtype,
                        )
                    )
    # coverage riders: max-logits output, and bwd block overrides
    qr, kr, lo, hi = masks["causal"]
    specs.append(
        AuditSpec(
            name="causal/bfloat16/g1/b256x512/emit_ml",
            q_ranges=qr, k_ranges=kr, d_lo=lo, d_hi=hi,
            sq=seq, sk=seq, hq=2, hk=2, blocks=(256, 512), emit_ml=True,
        )
    )
    specs.append(
        AuditSpec(
            name="causal/bfloat16/g4/b256x512/bwd_overrides",
            q_ranges=qr, k_ranges=kr, d_lo=lo, d_hi=hi,
            sq=seq, sk=seq, hq=8, hk=2, blocks=(256, 512),
            dq_blocks=(128, 512), dkv_blocks=(256, 256),
        )
    )
    # ragged seqlen: tiles overhang the true extent, so K3 must prove the
    # maps stay inside the PADDED operands and the padding columns of the
    # audit report are non-trivially exercised
    ragged = seq - seq // 8
    qr, kr, lo, hi = _canonical_masks(ragged)["causal"]
    specs.append(
        AuditSpec(
            name="causal_ragged/bfloat16/g2/b256x512",
            q_ranges=qr, k_ranges=kr, d_lo=lo, d_hi=hi,
            sq=ragged, sk=ragged, hq=4, hk=2, blocks=(256, 512),
        )
    )
    # fragmented-mask riders: partial tiles dominate, so the extent half
    # of K3 (check_k3_extents) is exercised on non-trivial live
    # sub-rectangles. The coarse-block variants are the extent-clamped
    # single-pass shape; the fine-block variants are what the mixed
    # dispatch's fragmented branch runs.
    for mask_name, (qr, kr, lo, hi) in _fragmented_masks(seq).items():
        for blocks, tag in (((256, 512), "coarse"), ((128, 128), "fine")):
            for g in (1, 4):
                specs.append(
                    AuditSpec(
                        name=(
                            f"{mask_name}/bfloat16/g{g}/"
                            f"b{blocks[0]}x{blocks[1]}/{tag}"
                        ),
                        q_ranges=qr, k_ranges=kr, d_lo=lo, d_hi=hi,
                        sq=seq, sk=seq, hq=2 * g, hk=2, blocks=blocks,
                    )
                )
    return specs


def run_kernel_audit(
    corpus: list[AuditSpec] | None = None,
    report: VerifyReport | None = None,
) -> tuple[VerifyReport, list[dict]]:
    """The full K1-K5 audit: discovery completeness, per-config contract
    capture + checks, source-level K2/K4, repo-level K5, and the abstract
    reachable-space K1 sweep. Returns (report, per-config rows)."""
    report = report or VerifyReport()
    corpus = corpus if corpus is not None else golden_corpus()

    sites = discover_pallas_sites()
    declared = _pallas_contracts()
    for site in sites:
        if site.kernel_name not in declared:
            report.add(
                "K2", ERROR, f"{site.relpath}:{site.line}",
                f"pallas_call site (kernel '{site.kernel_name}', wrapper "
                f"'{site.wrapper}') has no PALLAS_CONTRACTS entry — "
                f"annotate it so K2/K4 can check it",
            )

    check_kernel_sources(report)
    check_env_keys(report)

    rows: list[dict] = []
    captured_kernels: set[str] = set()
    for spec in corpus:
        for contract in capture_ffa_contracts(spec):
            captured_kernels.add(contract.kernel_name)
            site = f"{spec.name}:{contract.kernel_name}"
            check_contract(report, contract, site)
            info = _contract_shape_info(contract)
            row = {
                "config": spec.name,
                "kernel": contract.kernel_name,
                "grid": list(contract.grid),
                "vmem_bytes": _declared_bytes(contract),
                "vmem_total_bytes": ffa_kernel_residency(
                    info["kind"], info["bq"], info["bk"], info["d"],
                    head_dim_v=info["dv"], dtype_bytes=info["itemsize"],
                    group=info["g"], packed=info["packed"],
                    emit_ml=info["emit_ml"],
                ),
                "vmem_allowed_bytes": VMEM_ALLOWED_BYTES,
            }
            row.update(padding_stats(contract, spec.sq, spec.sk))
            rows.append(row)

    # paged-decode corpus: no plan metadata (padding_stats does not apply —
    # the page grid is dense by construction; dead pages are length-masked)
    for dspec in decode_corpus():
        for contract in capture_decode_contracts(dspec):
            captured_kernels.add(contract.kernel_name)
            site = f"{dspec.name}:{contract.kernel_name}"
            check_contract(report, contract, site)
            info = _contract_shape_info(contract)
            rows.append(
                {
                    "config": dspec.name,
                    "kernel": contract.kernel_name,
                    "grid": list(contract.grid),
                    "vmem_bytes": _declared_bytes(contract),
                    "vmem_total_bytes": ffa_kernel_residency(
                        info["kind"], info["bq"], info["bk"], info["d"],
                        head_dim_v=info["dv"], dtype_bytes=info["itemsize"],
                    ),
                    "vmem_allowed_bytes": VMEM_ALLOWED_BYTES,
                }
            )

    # block-sparse corpus: like decode, no plan metadata — the chunk grid
    # is exactly the top-k selection, dense by construction
    for bspec in bsp_corpus():
        for contract in capture_bsp_contracts(bspec):
            captured_kernels.add(contract.kernel_name)
            site = f"{bspec.name}:{contract.kernel_name}"
            check_contract(report, contract, site)
            info = _contract_shape_info(contract)
            rows.append(
                {
                    "config": bspec.name,
                    "kernel": contract.kernel_name,
                    "grid": list(contract.grid),
                    "vmem_bytes": _declared_bytes(contract),
                    "vmem_total_bytes": ffa_kernel_residency(
                        info["kind"], info["bq"], info["bk"], info["d"],
                        head_dim_v=info["dv"], dtype_bytes=info["itemsize"],
                    ),
                    "vmem_allowed_bytes": VMEM_ALLOWED_BYTES,
                }
            )

    # the scan's two calls: no plan metadata, a dense (groups, chunks) grid
    for contract in capture_ssd_contracts():
        captured_kernels.add(contract.kernel_name)
        check_scan_contract(report, contract, f"ssd:{contract.kernel_name}")
        rows.append(
            {
                "config": "ssd",
                "kernel": contract.kernel_name,
                "grid": list(contract.grid),
                "vmem_bytes": _declared_bytes(contract),
                # no model of the body's intermediates: K1's own margin
                "vmem_total_bytes": 2 * _declared_bytes(contract),
                "vmem_allowed_bytes": VMEM_ALLOWED_BYTES,
            }
        )

    # the grouped matmul's two bodies at the hybrid cell's shapes: a grid
    # of (column blocks, live row-tile visits)
    for contract in capture_grouped_contracts():
        captured_kernels.add(contract.kernel_name)
        check_grouped_contract(
            report, contract, f"grouped:{contract.kernel_name}")
        rows.append(
            {
                "config": "moe_grouped",
                "kernel": contract.kernel_name,
                "grid": list(contract.grid),
                "vmem_bytes": _declared_bytes(contract),
                "vmem_total_bytes": grouped_residency(contract),
                "vmem_allowed_bytes": VMEM_ALLOWED_BYTES,
            }
        )

    site_kernels = {
        s.kernel_name for s in sites if s.kernel_name in declared
    }
    for missing in sorted(site_kernels - captured_kernels):
        report.add(
            "K1", ERROR, f"kernels/:{missing}",
            f"kernel '{missing}' has a pallas_call site but no corpus "
            f"config exercised it — the audit is not complete",
        )

    sweep = check_reachable_space(report, _SEQ, _SEQ)
    rows.append({"config": "reachable_space_sweep", **sweep})
    return report, rows


# ---------------------------------------------------------------------------
# seeded mutations — the checker's own regression proof
# ---------------------------------------------------------------------------

# a minimal clean kernel in the house style; the K2 mutation deletes its
# init block. Kept source-level so the mutation exercises the same AST
# path as the real kernels.
_TOY_KERNEL_SRC = '''
def _toy_kernel(qt_ref, kt_ref, meta_ref, x_ref, o_ref, acc_scr):
    w = pl.program_id(1)
    is_first = meta_ref[w, IS_FIRST]
    is_last = meta_ref[w, IS_LAST]

    @pl.when(is_first == 1)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    acc_scr[:] += jax.lax.dot_general(
        x_ref[:], x_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(is_last == 1)
    def _():
        o_ref[:] = acc_scr[:].astype(o_ref.dtype)
'''

_TOY_CONTRACTS = {
    "_toy_kernel": dict(
        wrapper="_toy",
        scratch=("acc_scr",),
        outputs=("o_ref",),
        out_dtypes=("input",),
        init_guard="is_first",
        flush_guard="is_last",
        group_inner=None,
    ),
}

# minimal fused-style kernel: a scratch accumulator (is_first/is_last)
# PLUS a revisit-accumulated output (qvf/qvl) read back from its aliased
# operand — the shape the deleted_revisit_init and
# deleted_revisit_readback mutations operate on
_TOY_FUSED_KERNEL_SRC = '''
def _toy_fused_kernel(qt_ref, kt_ref, meta_ref, x_ref, dqin_ref, dq_ref,
                      o_ref, acc_scr):
    w = pl.program_id(1)
    is_first = meta_ref[w, IS_FIRST]
    is_last = meta_ref[w, IS_LAST]
    qvf = meta_ref[w, QVF]
    qvl = meta_ref[w, QVL]

    @pl.when(is_first == 1)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(qvf == 1)
    def _():
        dq_ref[0] = jnp.zeros((8, 8), jnp.float32)

    moved = (w == 0) | (qt_ref[w] != qt_ref[w - 1])

    @pl.when(moved & (qvf == 0))
    def _():
        dq_ref[0] = dqin_ref[0]

    contrib = jax.lax.dot_general(
        x_ref[:], x_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[:] += contrib
    dq_ref[0] += contrib

    @pl.when(is_last == 1)
    def _():
        o_ref[:] = acc_scr[:].astype(o_ref.dtype)

    @pl.when(qvl == 1)
    def _():
        dq_ref[0] = dq_ref[0] * 2.0
'''

_TOY_FUSED_CONTRACTS = {
    "_toy_fused_kernel": dict(
        wrapper="_toy_fused",
        scratch=("acc_scr",),
        outputs=("dq_ref", "o_ref"),
        out_dtypes=("f32", "input"),
        init_guard="is_first",
        flush_guard="is_last",
        group_inner=None,
        revisit=dict(out="dq_ref", init_guard="qvf", flush_guard="qvl",
                     readback="dqin_ref"),
    ),
}


def _mutation_spec() -> AuditSpec:
    # hq (8) > num_q_tiles (4) so the swapped-axes mutation is provably
    # out of bounds on the q-tile axis
    qr, kr, lo, hi = _canonical_masks(512)["causal"]
    return AuditSpec(
        name="mutation/causal", q_ranges=qr, k_ranges=kr, d_lo=lo, d_hi=hi,
        sq=512, sk=512, hq=8, hk=8, blocks=(128, 128),
    )


def run_seeded_mutations() -> list[dict]:
    """Apply each seeded defect to a clean contract/source/key-set and
    report which rules fire. A healthy checker fires EXACTLY the expected
    rule per mutation — the test suite and ``kernel_audit --selftest``
    both assert on this."""
    from types import SimpleNamespace

    base = next(
        c for c in capture_ffa_contracts(_mutation_spec())
        if c.kernel_name == "_fwd_kernel"
    )
    results: list[dict] = []

    def run(name: str, expected: str, check) -> None:
        report = VerifyReport()
        check(report)
        fired = report.fired_rules()
        results.append(
            {
                "mutation": name,
                "expected_rule": expected,
                "fired_rules": sorted(fired),
                "ok": fired == {expected},
            }
        )

    def oversized(report: VerifyReport) -> None:
        mut = replace(
            base,
            scratch=tuple(
                ((shape[0] * 64,) + tuple(shape[1:]), dtype)
                for shape, dtype in base.scratch
            ),
        )
        check_contract(report, mut, "mutation:oversized_scratch")

    def swapped(report: VerifyReport) -> None:
        q_spec = base.in_specs[0]
        orig = q_spec.index_map
        shim = SimpleNamespace(
            block_shape=q_spec.block_shape,
            # swap the head and q-tile outputs of the real map
            index_map=lambda *a: (
                lambda o: (o[1], o[0]) + tuple(o[2:])
            )(orig(*a)),
        )
        mut = replace(base, in_specs=(shim,) + tuple(base.in_specs[1:]))
        check_contract(report, mut, "mutation:swapped_index_map")

    def no_init(report: VerifyReport) -> None:
        src = _TOY_KERNEL_SRC
        start = src.index("    @pl.when(is_first == 1)")
        end = src.index("    acc_scr[:] +=")
        check_kernel_sources(
            report, src[:start] + src[end:], _TOY_CONTRACTS, "mutation.py"
        )

    def bf16_scratch(report: VerifyReport) -> None:
        mut = replace(
            base,
            scratch=tuple(
                (shape, "bfloat16") for shape, _ in base.scratch
            ),
        )
        check_contract(report, mut, "mutation:bf16_scratch")

    def unlisted_key(report: VerifyReport) -> None:
        check_env_keys(
            report,
            consumed={"MAGI_ATTENTION_UNLISTED_KNOB": {"ffa.py"}},
        )

    def bad_extent(report: VerifyReport) -> None:
        # zero one real item's live k extent: stays aligned and in-bounds,
        # so ONLY the host-recomputation equality can catch the clamp path
        # silently skipping a live chunk
        meta = base.prefetch[2].copy()
        w = int(np.nonzero(meta[:, QE] > meta[:, QS])[0][0])
        meta[w, EK1] = meta[w, EK1] - LANE_QUANTUM
        mut = replace(
            base, prefetch=(base.prefetch[0], base.prefetch[1], meta)
        )
        check_contract(report, mut, "mutation:corrupted_extent_row")

    def no_revisit_init(report: VerifyReport) -> None:
        # delete the qvf first-visit zeroing of the revisit-accumulated
        # output — interpret mode still passes (the donated output buffer
        # happens to start zeroed) but hardware VMEM is undefined on the
        # first visit, so only K2's revisit rule can catch it
        src = _TOY_FUSED_KERNEL_SRC
        start = src.index("    @pl.when(qvf == 1)")
        end = src.index("    moved = ")
        check_kernel_sources(
            report, src[:start] + src[end:], _TOY_FUSED_CONTRACTS,
            "mutation.py",
        )

    def no_revisit_readback(report: VerifyReport) -> None:
        # delete the later-visit copy-in of the aliased dq operand: the
        # interpreter tier-1 runs keeps an output's contents between
        # visits and still passes, while the chip accumulates on whatever
        # tile the window held last (PR 30) — K2's read-back rule catches it
        src = _TOY_FUSED_KERNEL_SRC
        start = src.index("    @pl.when(moved & (qvf == 0))")
        end = src.index("    contrib = ")
        check_kernel_sources(
            report, src[:start] + src[end:], _TOY_FUSED_CONTRACTS,
            "mutation.py",
        )

    def oob_page_table(report: VerifyReport) -> None:
        # point one page-table entry one past the last page: gather_kv's
        # maximum(table, 0) clamp only rescues -1 sentinels, so an
        # oversized id escapes the k/v operands — only the K3 index-map
        # bounds eval over the real prefetch can catch it
        dbase = next(
            c for c in capture_decode_contracts(decode_corpus()[0])
            if c.kernel_name == "_paged_decode_kernel"
        )
        num_pages = dbase.operands[1][0][0]  # k_pages page axis
        table = dbase.prefetch[0].copy()
        table[0, 0] = num_pages
        mut = replace(
            dbase, prefetch=(table,) + tuple(dbase.prefetch[1:])
        )
        check_contract(report, mut, "mutation:oob_page_table")

    def misrouted_scale_prefetch(report: VerifyReport) -> None:
        # swap the (page, head) outputs of the int8 per-page scale index
        # map: the head coordinate (< hk) silently fits the page axis, but
        # real page ids land on the hk-wide head axis of the (num_pages,
        # hk) scale array — the decode output would mix WRONG pages'
        # scales without faulting, and only the K3 bounds eval over the
        # real page-table prefetch catches the escape
        ibase = next(
            c for c in capture_decode_contracts(
                next(s for s in decode_corpus() if s.variant == "int8")
            )
            if c.kernel_name == "_paged_decode_int8_kernel"
        )
        ks_spec = ibase.in_specs[3]
        orig = ks_spec.index_map
        shim = SimpleNamespace(
            block_shape=ks_spec.block_shape,
            index_map=lambda *a: (lambda o: (o[1], o[0]))(orig(*a)),
        )
        mut = replace(
            ibase,
            in_specs=tuple(ibase.in_specs[:3])
            + (shim,)
            + tuple(ibase.in_specs[4:]),
        )
        check_contract(report, mut, "mutation:misrouted_scale_prefetch")

    def oob_block_table(report: VerifyReport) -> None:
        # point one chunk-table entry one past the last chunk: the block-
        # sparse index maps consume the table UNclamped (the public wrapper
        # audits concrete tables, but a traced top-k bypasses that), so
        # only the K3 index-map bounds eval over the real prefetch catches
        # the out-of-range stream
        bbase = next(
            c for c in capture_bsp_contracts(bsp_corpus()[0])
            if c.kernel_name == "_bsp_fwd_kernel"
        )
        n_chunks = bbase.operands[1][0][0]  # k_c chunk axis
        table = bbase.prefetch[0].copy()
        table[0, 0, 0] = n_chunks
        mut = replace(bbase, prefetch=(table,) + tuple(bbase.prefetch[1:]))
        check_contract(report, mut, "mutation:oob_block_table")

    run("oversized_scratch", "K1", oversized)
    run("swapped_index_map_axes", "K3", swapped)
    run("missing_accumulator_init", "K2", no_init)
    run("deleted_revisit_init", "K2", no_revisit_init)
    run("deleted_revisit_readback", "K2", no_revisit_readback)
    run("bf16_accumulator", "K4", bf16_scratch)
    run("unlisted_env_key", "K5", unlisted_key)
    run("corrupted_extent_row", "K3", bad_extent)
    run("oob_page_table", "K3", oob_page_table)
    run("misrouted_scale_prefetch", "K3", misrouted_scale_prefetch)
    run("oob_block_table", "K3", oob_block_table)
    return results
