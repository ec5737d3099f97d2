"""The post-processing tools must work BEFORE a chip run lands.

Chip time is budgeted; the script that turns a run's CSV rows into a
decision (fit_tile_overhead's least-squares) runs afterwards. These tests
pin it on synthetic data so a tooling bug cannot waste the next run.
"""

import csv
import os

import numpy as np

from tests.test_support.script_loading import load_script

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


class TestFitTileOverhead:
    def _write_rows(self, path, rows):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        keys = sorted({k for r in rows for k in r})
        with open(path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=keys)
            w.writeheader()
            w.writerows(rows)

    def test_recovers_planted_overhead(self, tmp_path, monkeypatch):
        """Synthesize ms(bq,bk) = alpha*W*bq*bk + beta*W rows at the real
        seq-8192 work counts; the fit must recover beta/alpha."""
        fit = load_script(
            os.path.join(ROOT, "scripts", "fit_tile_overhead.py"),
            "fit_tile_overhead",
        )
        from magiattention_tpu.kernels.mask_utils import types_to_bands
        from magiattention_tpu.kernels.tile_policy import count_ffa_work

        S = fit.S
        qr = np.array([[0, S]], np.int32)
        kr = np.array([[0, S]], np.int32)
        lo, hi = types_to_bands(qr, kr, np.array([1], np.int32))
        alpha, beta = 2.5e-9, 1.5e-3  # OVERHEAD_ELEMS = 600k
        rows = []
        for bq, bk in [(256, 512), (512, 512), (512, 1024), (1024, 1024)]:
            w = count_ffa_work(qr, kr, lo, hi, S, S, bq, bk)
            rows.append({
                "probe": f"ffa_fwd_bq{bq}_bk{bk}",
                "ms": alpha * w * bq * bk + beta * w,
                "commit": "abc1234", "len_short": "8", "len_long": "32",
            })
        # contamination rows the guards must reject: wrong shape stamp,
        # missing stamp, different commit with fewer tilings
        rows.append({"probe": "ffa_fwd_bq512_bk512", "ms": 999.0,
                     "commit": "abc1234", "len_short": "24",
                     "len_long": "96"})
        rows.append({"probe": "ffa_fwd_bq256_bk512", "ms": 123.0,
                     "commit": "abc1234", "len_short": "",
                     "len_long": ""})
        rows.append({"probe": "ffa_fwd_bq512_bk512", "ms": 5.0,
                     "commit": "zzz9999", "len_short": "8",
                     "len_long": "32"})
        hist = tmp_path / "true_rate.csv"
        self._write_rows(str(hist), rows)
        monkeypatch.setattr(fit, "HIST", str(hist))

        import contextlib
        import io

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = fit.main()
        out = buf.getvalue()
        assert rc == 0, out
        assert "abc1234 (4 tilings)" in out
        got = float(out.split("OVERHEAD_ELEMS ~= ")[1]
                    .split()[0].replace(",", ""))
        want = beta / alpha
        assert abs(got - want) / want < 1e-6, (got, want)

    def test_refuses_degenerate_fit(self, tmp_path, monkeypatch):
        """Noise implying negative overhead must refuse, not recommend."""
        fit = load_script(
            os.path.join(ROOT, "scripts", "fit_tile_overhead.py"),
            "fit_tile_overhead",
        )
        from magiattention_tpu.kernels.mask_utils import types_to_bands
        from magiattention_tpu.kernels.tile_policy import count_ffa_work

        S = fit.S
        qr = np.array([[0, S]], np.int32)
        kr = np.array([[0, S]], np.int32)
        lo, hi = types_to_bands(qr, kr, np.array([1], np.int32))
        alpha, beta = 1e-7, -1e-3  # beta < 0: negative implied overhead
        rows = []
        for bq, bk in [(256, 512), (512, 512), (1024, 1024)]:
            w = count_ffa_work(qr, kr, lo, hi, S, S, bq, bk)
            rows.append({
                "probe": f"ffa_fwd_bq{bq}_bk{bk}",
                "ms": alpha * w * bq * bk + beta * w,
                "commit": "abc1234", "len_short": "8", "len_long": "32",
            })
        hist = tmp_path / "true_rate.csv"
        self._write_rows(str(hist), rows)
        monkeypatch.setattr(fit, "HIST", str(hist))

        import contextlib
        import io

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = fit.main()
        assert rc == 1
        assert "degenerate fit" in buf.getvalue()  # THE guard, not rc=1
