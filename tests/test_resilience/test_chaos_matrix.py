"""Chaos matrix (docs/resilience.md): every registered injection site
either RECOVERS through its documented fallback (finite result, allclose
to the clean path) or RAISES its documented typed error — never a silent
NaN. Run via ``make chaos`` (CPU-only, Pallas interpret mode)."""

import numpy as np
import pytest

from magiattention_tpu.resilience.errors import (
    FallbackExhaustedError,
    InjectedFault,
    NumericGuardError,
)
from magiattention_tpu.resilience.fallback import run_calc_attn, tile_ladder

from tests.test_resilience.conftest import make_mesh, make_mgr, run_step

pytestmark = pytest.mark.chaos


# ---------------------------------------------------------------------------
# site: kernel_lowering — FFA pallas dispatch (kernels/ffa.py)
# ---------------------------------------------------------------------------


class TestKernelLowering:
    def test_recovers_via_fallback_chain(self, monkeypatch):
        base_out, _ = run_step(make_mgr())
        monkeypatch.setenv(
            "MAGI_ATTENTION_FAULT_INJECT", "kernel_lowering:count=1"
        )
        monkeypatch.setenv("MAGI_ATTENTION_FALLBACK", "1")
        mgr = make_mgr()
        out, lse = run_step(mgr)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(base_out), atol=2e-5, rtol=2e-5
        )
        # degradation is sticky: the next step reuses the surviving path
        # without re-failing (the fault already burned its count anyway)
        out2, _ = run_step(mgr, seed=1)
        assert np.isfinite(np.asarray(out2)).all()

    def test_raises_typed_without_fallback(self, monkeypatch):
        monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "kernel_lowering")
        mgr = make_mgr()
        with pytest.raises(InjectedFault, match="kernel_lowering"):
            run_step(mgr)


# ---------------------------------------------------------------------------
# kernel ladder unit semantics (no jax needed: a scripted fake runtime)
# ---------------------------------------------------------------------------


class _FakeRuntime:
    def __init__(self, fail_first_n: int):
        self._bq, self._bk = 512, 512
        self._auto_tile_pending = True
        self._backend_override = None
        self.builds = []
        self.calls = 0
        self._fail_first = fail_first_n

    def _build_plans(self, bq, bk):
        self.builds.append((bq, bk))

    def _calc_attn_impl(self, q, k, v, return_max_logits):
        self.calls += 1
        if self.calls <= self._fail_first:
            raise InjectedFault("kernel_lowering", self.calls)
        return ("out", "lse")


class TestLadderSemantics:
    def test_ladder_is_descending_and_below_current(self):
        rungs = tile_ladder(512, 512)
        areas = [bq * bk for bq, bk in rungs]
        assert areas == sorted(areas, reverse=True)
        assert all(a < 512 * 512 for a in areas)
        assert tile_ladder(128, 128) == []  # already at the bottom

    def test_descends_until_a_rung_survives(self, monkeypatch):
        monkeypatch.setenv("MAGI_ATTENTION_FALLBACK", "1")
        rt = _FakeRuntime(fail_first_n=2)
        out = run_calc_attn(rt, None, None, None)
        assert out == ("out", "lse")
        # initial call + rung0 failed; rung1 (the 2nd ladder entry) won
        assert rt.builds == tile_ladder(512, 512)[:2]
        assert rt._auto_tile_pending is False
        # a rung is an explicit tile: the ``ffa_tiles`` note says "pin"
        assert rt._tile_source == "pin"
        assert rt._backend_override is None

    def test_reference_backend_is_the_last_rung(self, monkeypatch):
        monkeypatch.setenv("MAGI_ATTENTION_FALLBACK", "1")
        n_rungs = len(tile_ladder(512, 512))
        rt = _FakeRuntime(fail_first_n=1 + n_rungs)  # every FFA try fails
        out = run_calc_attn(rt, None, None, None)
        assert out == ("out", "lse")
        assert rt._backend_override == "sdpa_online"

    def test_exhaustion_raises_typed_with_cause(self, monkeypatch):
        monkeypatch.setenv("MAGI_ATTENTION_FALLBACK", "1")
        rt = _FakeRuntime(fail_first_n=10_000)
        with pytest.raises(FallbackExhaustedError) as ei:
            run_calc_attn(rt, None, None, None)
        assert isinstance(ei.value.__cause__, InjectedFault)
        assert rt._backend_override is None  # failed override rolled back

    def test_no_fallback_flag_propagates_unchanged(self):
        rt = _FakeRuntime(fail_first_n=1)
        with pytest.raises(InjectedFault):
            run_calc_attn(rt, None, None, None)
        assert rt.builds == []  # the ladder never engaged


# ---------------------------------------------------------------------------
# site: vmem_check — tile-policy scoring (kernels/tile_policy.py)
# ---------------------------------------------------------------------------


class TestVmemCheck:
    def test_recovers_with_default_blocks(self, monkeypatch):
        base_out, _ = run_step(make_mgr())
        monkeypatch.setenv("MAGI_ATTENTION_FFA_AUTO_TILE", "1")
        monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "vmem_check")
        monkeypatch.setenv("MAGI_ATTENTION_FALLBACK", "1")
        out, _ = run_step(make_mgr())
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(base_out), atol=2e-5, rtol=2e-5
        )

    def test_raises_typed_without_fallback(self, monkeypatch):
        monkeypatch.setenv("MAGI_ATTENTION_FFA_AUTO_TILE", "1")
        monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "vmem_check")
        mgr = make_mgr()
        with pytest.raises(InjectedFault, match="vmem_check"):
            run_step(mgr)


# ---------------------------------------------------------------------------
# site: dynamic_plan_solve — qo-comm planner (meta/_make_attn_meta.py)
# ---------------------------------------------------------------------------


class TestDynamicPlanSolve:
    def test_falls_back_to_static_plan(self, monkeypatch):
        base_out, _ = run_step(make_mgr())  # plain static baseline
        monkeypatch.setenv("MAGI_ATTENTION_QO_COMM", "1")
        monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "dynamic_plan_solve")
        monkeypatch.setenv("MAGI_ATTENTION_FALLBACK", "1")
        mgr = make_mgr()
        assert mgr.dynamic_plan is None  # the dynamic solve was abandoned
        assert mgr.calc_meta is not None  # ... for the static solver plan
        out, _ = run_step(mgr)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(base_out), atol=2e-5, rtol=2e-5
        )

    def test_raises_typed_without_fallback(self, monkeypatch):
        monkeypatch.setenv("MAGI_ATTENTION_QO_COMM", "1")
        monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "dynamic_plan_solve")
        with pytest.raises(InjectedFault, match="dynamic_plan_solve"):
            make_mgr()


# ---------------------------------------------------------------------------
# site: comm_plan_build — static comm-plan build (meta/_make_attn_meta.py)
# ---------------------------------------------------------------------------


class TestCommPlanBuild:
    def test_recovers_via_bounded_retry(self, monkeypatch):
        from magiattention_tpu.api import init_dist_attn_runtime_key
        from magiattention_tpu.dist_attn_runtime_mgr import (
            DistAttnRuntimeDict,
        )

        monkeypatch.setenv(
            "MAGI_ATTENTION_FAULT_INJECT", "comm_plan_build:count=1"
        )
        monkeypatch.setenv("MAGI_ATTENTION_FALLBACK", "1")
        mesh = make_mesh()
        key = init_dist_attn_runtime_key(
            [[0, 256]], [[0, 256]], ["causal"], 256, 256, 16, mesh=mesh
        )
        d = DistAttnRuntimeDict(maxsize=4)
        mgr = d.get_or_create(key, mesh)  # attempt 1 fails, retry succeeds
        assert mgr.calc_meta is not None
        assert len(d) == 1

    def test_raises_typed_without_fallback(self, monkeypatch):
        monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "comm_plan_build")
        with pytest.raises(InjectedFault, match="comm_plan_build"):
            make_mgr()


# ---------------------------------------------------------------------------
# site: nan_output — post-kernel corruption caught by the numeric guard
# ---------------------------------------------------------------------------


class TestNanOutput:
    def test_guard_raise_catches_corruption(self, monkeypatch):
        monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "nan_output")
        monkeypatch.setenv("MAGI_ATTENTION_NUMERIC_GUARD", "raise")
        mgr = make_mgr()
        with pytest.raises(NumericGuardError, match="calc_attn") as ei:
            run_step(mgr)
        assert "out" in ei.value.detail

    def test_guard_record_flags_without_raising(self, monkeypatch):
        monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "nan_output:step=1")
        monkeypatch.setenv("MAGI_ATTENTION_NUMERIC_GUARD", "record")
        out, _ = run_step(make_mgr())
        # the corruption went through (record policy), and is visible —
        # the guard's telemetry record is what makes it non-silent
        assert np.isnan(np.asarray(out)).any()

    def test_clean_run_passes_the_guard(self, monkeypatch):
        # guard armed, no fault: the sentinel must accept real outputs
        # (including the legal -inf LSE of any fully-masked rows)
        monkeypatch.setenv("MAGI_ATTENTION_NUMERIC_GUARD", "raise")
        out, _ = run_step(make_mgr())
        assert np.isfinite(np.asarray(out)).all()
