"""Sanity pins on the pre-registered roofline model (benchmarks/roofline.py).

The model's bands are the round's falsifiability contract — if the model
itself silently breaks (plan counts drift, a unit slips), the published
bands stop meaning anything. These tests pin the invariants the doc's
claims rest on, at a small shape so the fast tier stays fast.
"""

import os

import numpy as np

from tests.test_support.script_loading import load_script

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def _model():
    return load_script(
        os.path.join(ROOT, "benchmarks", "roofline.py"), "roofline"
    )


def _rows(mask_type, s=1024):
    m = _model()
    qr = np.array([[0, s]], np.int32)
    kr = np.array([[0, s]], np.int32)
    tm = np.array([mask_type], np.int32)
    area = s * (s + 1) // 2 if mask_type == 1 else s * s
    return m.model(f"t{mask_type}", qr, kr, tm, area,
                   s, s, 16, 8, 128, 512, 512)


def test_bands_well_formed():
    for rows in (_rows(0), _rows(1)):
        for r in rows:
            assert r["floor_ms"] > 0
            assert r["ms_lo"] < r["ms_hi"]
            assert r["tf_lo"] < r["tf_hi"]
            assert 0 < r["mfu_lo"] < r["mfu_hi"] <= 1
            assert r["gbytes"] > 0
            # the floor is max(compute, memory): never faster than the
            # pure-MXU time for the hardware flops
            peak = _model().PEAK * _model().AMBIENT
            flops_hw = (4 * r["area"] * 128 * 16
                        * (1 if r["phase"] == "fwd"
                           else _model().HW_FWD_BWD))
            assert r["floor_ms"] >= flops_hw / peak * 1e3 * 0.999


def test_causal_full_rate_ratio_near_one():
    """The doc's corollary 1: rates are area-normalized, so the
    predicted causal/full TFLOP/s ratio is ~1 at the grid seqlen (4096;
    at much smaller seqlens tile-granularity padding legitimately drops
    the causal rate — the corollary is a statement about the published
    configs, not all shapes). Lower bound 0.80: anchoring AMBIENT to the
    measured 208 TF/s ceiling (vs the earlier 0.957 derate) speeds the
    compute floor enough that causal fwd at 4096 crosses into being
    HBM-bound, where its tile-padding traffic costs a few percent."""
    full = {r["phase"]: r for r in _rows(0, s=4096)}
    caus = {r["phase"]: r for r in _rows(1, s=4096)}
    for phase in ("fwd", "fwdbwd"):
        ratio = caus[phase]["tf_hi"] / full[phase]["tf_hi"]
        assert 0.80 <= ratio <= 1.1, (phase, ratio)


def test_fwdbwd_slower_than_fwd_but_more_flops():
    rows = {r["phase"]: r for r in _rows(1)}
    assert rows["fwdbwd"]["floor_ms"] > rows["fwd"]["floor_ms"]
    assert rows["fwdbwd"]["gbytes"] > rows["fwd"]["gbytes"]


def test_overhead_cross_check_structure():
    """The 9.92-vs-26.87 analysis: each recorded row's implied overhead
    must be POSITIVE (measured slower than the modeled kernel band) —
    that is what makes the pre-slope pair inadmissible."""
    m = _model()
    rows = []
    for mask in ("full", "causal"):
        s = 4096
        qr = np.array([[0, s]], np.int32)
        kr = np.array([[0, s]], np.int32)
        tm = np.array([1 if mask == "causal" else 0], np.int32)
        area = s * (s + 1) // 2 if mask == "causal" else s * s
        rows.extend(m.model(f"grid_{mask}_4096", qr, kr, tm, area,
                            s, s, 16, 8, 128, 512, 512))
    lines = m.overhead_cross_check(rows)
    assert len(lines) == 2
    for line in lines:
        # "implied fixed overhead A-B ms": both bounds positive
        span = line.rsplit("overhead", 1)[1].replace("ms", "").strip()
        lo, hi = (float(x) for x in span.split("-"))
        assert 0 < lo < hi, line
