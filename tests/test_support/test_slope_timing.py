"""Deterministic pins for the slope-timing math in benchmarking/bench.py.

The TPU branch of do_bench_scan_slope (paired two-trip-count slopes,
median, noise guard, credibility floor) is the measurement mechanics every
silicon number flows through; a silent regression there corrupts whole
chip windows. These tests fake the backend and the scan runners so the
arithmetic is pinned without hardware.
"""

import numpy as np
import pytest

import magiattention_tpu.benchmarking.bench as bench


@pytest.fixture()
def fake_tpu(monkeypatch):
    monkeypatch.setattr(bench.jax, "default_backend", lambda: "tpu")


def _fake_runners(monkeypatch, per_step_ms, launch_ms_seq):
    """Each runner call consumes the next fixed launch cost from
    launch_ms_seq: total seconds = (launch + per_step*length) / 1e3."""
    calls = iter(launch_ms_seq)

    def make(body, carry0, length):
        def run():
            return (next(calls) + per_step_ms * length) / 1e3

        return run

    monkeypatch.setattr(bench, "_make_scan_runner", make)


class TestSlopeTiming:
    def test_slope_cancels_fixed_launch_cost(self, fake_tpu, monkeypatch):
        # constant 170 ms launch cost, true per-step 2.0 ms
        _fake_runners(monkeypatch, 2.0, [170.0] * 6)
        ms = bench.do_bench_scan_slope(lambda c: c, 0, lengths=(8, 32),
                                       reps=3)
        assert ms == pytest.approx(2.0)

    def test_median_rejects_one_drifted_pair(self, fake_tpu, monkeypatch):
        # rep 2's long scan sees +60 ms drift -> that rep's slope is
        # polluted; the median of three slopes must still be exact
        _fake_runners(
            monkeypatch, 2.0, [170.0, 170.0, 170.0, 230.0, 170.0, 170.0]
        )
        ms = bench.do_bench_scan_slope(lambda c: c, 0, lengths=(8, 32),
                                       reps=3)
        assert ms == pytest.approx(2.0)

    def test_noise_guard_falls_back_to_long_upper_bound(
        self, fake_tpu, monkeypatch
    ):
        # long consistently FASTER than short (memoization/thermal):
        # negative slope -> fall back to t_long/length
        _fake_runners(
            monkeypatch, 0.0, [200.0, 64.0, 200.0, 64.0, 200.0, 64.0]
        )
        ms = bench.do_bench_scan_slope(lambda c: c, 0, lengths=(8, 32),
                                       reps=3)
        assert ms == pytest.approx(64.0 / 32)

    def test_credibility_floor_rejects_unphysical_slope(
        self, fake_tpu, monkeypatch
    ):
        # slope says 0.5 ms/step but the flop count says nothing under
        # 2.0 ms is physical -> fall back to the long upper bound
        _fake_runners(monkeypatch, 0.5, [170.0] * 6)
        ms = bench.do_bench_scan_slope(
            lambda c: c, 0, lengths=(8, 32), reps=3, min_credible_ms=2.0
        )
        assert ms == pytest.approx((170.0 + 0.5 * 32) / 32)

    def test_floor_does_not_touch_physical_slopes(self, fake_tpu,
                                                  monkeypatch):
        _fake_runners(monkeypatch, 3.0, [170.0] * 6)
        ms = bench.do_bench_scan_slope(
            lambda c: c, 0, lengths=(8, 32), reps=3, min_credible_ms=2.0
        )
        assert ms == pytest.approx(3.0)


class TestCredibleFloor:
    def test_floor_matches_measured_ceiling(self):
        # the floor anchors to the silicon-MEASURED matmul ceiling (208,
        # true_rate.csv mm4096), not PEAK * slack — a genuine measurement
        # at the chip's real rate must never be classified unphysical
        from magiattention_tpu.benchmarking.perf_report import (
            MEASURED_CEILING_TFLOPS,
            credible_floor_ms,
        )

        flops = 1e12
        ms = credible_floor_ms(flops)
        implied_tflops = flops / (ms * 1e-3) / 1e12
        assert implied_tflops == pytest.approx(MEASURED_CEILING_TFLOPS)

    def test_off_tpu_raises(self, monkeypatch):
        # slope timing is a device measurement: on the CPU it must stop,
        # not switch to another timing method under the same name
        monkeypatch.setattr(bench.jax, "default_backend", lambda: "cpu")
        with pytest.raises(RuntimeError, match="times a TPU"):
            bench.do_bench_scan_slope(lambda c: c, 0, min_credible_ms=50.0)


def test_kv_bodies_preserve_aux_and_consume_grads():
    """CPU sanity for the carry-tuple helpers (the no-captured-constants
    bodies every large-operand harness must use)."""
    import jax
    import jax.numpy as jnp

    q = jnp.ones((4, 2), jnp.float32)
    k = jnp.full((4, 2), 2.0)
    v = jnp.full((4, 2), 3.0)
    w = jnp.full((4, 2), 0.5)

    fb = bench.make_fwd_kv_body(lambda q, k, v, w: (q @ k.T @ v) * w,
                                jnp.float32)
    o, k2, v2, w2 = fb((q, k, v, w))
    np.testing.assert_allclose(
        np.asarray(o), np.asarray((q @ k.T @ v) * w)
    )
    assert k2 is k and v2 is v and w2 is w

    g = jax.grad(lambda q, k, v: jnp.sum(q @ k.T @ v), argnums=(0, 1, 2))
    bb = bench.make_consume_all_grads_kv_body(g, jnp.float32)
    qn, k3, v3 = bb((q, k, v))
    assert k3 is k and v3 is v
    # dq enters scaled 1e-3; dk/dv enter only via the 1e-30 touch term
    assert float(jnp.max(jnp.abs(qn - q))) > 1e-6
