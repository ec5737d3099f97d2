"""Append-only perf history + round-over-round deltas.

TPU-native replacement for the reference's Benchmark/Mark/perf_report
harness (magi_attention/benchmarking/bench.py:372-1378, CSV + plots): every
measurement appends one row to a CSV under ``benchmarks/history/`` (kept in
git), so each chip window extends a comparable record instead of
overwriting a JSON blob. ``history_report`` renders the latest row per config
with a delta against the previous measurement of the same config.

Dual MFU convention (VERDICT r2 item 10): rows carry the reference's FLOP
counting (fwd = 4*area*d*hq, bwd = 2.5x) for comparability, plus the
hardware matmul convention (the TPU backward runs 3.5x the fwd matmul work
— separate dq and dkv passes, docs/performance.md) so kernel progress is
not obscured by accounting: ``hw_tflops = tflops * HW_FWD_BWD_RATIO``.
"""

from __future__ import annotations

import csv
import datetime
import os

HISTORY_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    "benchmarks",
    "history",
)

# actual matmul work per reported (reference-convention) FLOP for fwd+bwd:
# reported = fwd * 3.5 (fwd + 2.5x bwd), executed = fwd * 4.5 (fwd + 3.5x
# bwd: dq pass 3 matmuls + dkv pass 4 vs fwd's 2)
HW_FWD_BWD_RATIO = 4.5 / 3.5

# Published peaks of one chip, keyed by the ``device_kind`` JAX reports
# (source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB
# of HBM at 819 GB/s). The ONE table every measuring harness divides by; a
# device that is not in it is an error, never a default.
DEVICE_PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0, "hbm_gb": 16.0},
}


def peak_tflops(device_kind: str) -> float:
    """bf16 peak TFLOP/s of the device a measurement just ran on
    (``jax.devices()[0].device_kind``); raises on an unknown kind."""
    try:
        return DEVICE_PEAKS[device_kind]["bf16_tflops"]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {device_kind!r}: add it to "
            f"perf_report.DEVICE_PEAKS with its source (known: "
            f"{sorted(DEVICE_PEAKS)})"
        ) from None


# the v5e figure, for the host-side MODELS of that chip (scaling_model,
# roofline); anything that measures calls peak_tflops(device_kind) instead
PEAK_TFLOPS = DEVICE_PEAKS["TPU v5 lite"]["bf16_tflops"]

# silicon-MEASURED matmul ceiling of the attached chip (true_rate.csv
# mm4096 slope: 207.98 TF/s ≈ 105.6% of nominal) — the ONE anchor for
# credibility floors and the roofline's ambient derate. Anchoring to the
# measured ceiling (not PEAK * slack) means a genuine measurement at the
# chip's real matmul rate can never be classified unphysical.
MEASURED_CEILING_TFLOPS = 208.0


def credible_floor_ms(
    flops: float, ceiling_tflops: float = MEASURED_CEILING_TFLOPS
) -> float:
    """Physical lower bound on a measurement of ``flops`` of matmul work:
    time implying a rate above the measured chip ceiling is unphysical
    (pass as ``do_bench_scan_slope(min_credible_ms=...)``). ``flops``
    must be EXECUTED flops — for fwd+bwd that is 4.5x fwd
    (HW_FWD_BWD_RATIO x the reference-convention 3.5x), or the floor sits
    ~29% below the physical bound it claims."""
    return flops / (ceiling_tflops * 1e9)


def append_row(name: str, row: dict) -> str:
    """Append one measurement to ``benchmarks/history/<name>.csv``.

    Adds the ``utc`` column; ``commit`` is the caller's to pass in ``row``
    (a chip run's copy of the tree is not a git repository) and reads
    ``unknown`` otherwise. The header is the union of all keys ever seen
    for this file (the file is rewritten with an extended header when a
    new key appears — files are small).
    """
    os.makedirs(HISTORY_DIR, exist_ok=True)
    path = os.path.join(HISTORY_DIR, f"{name}.csv")
    full = {
        "utc": datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y-%m-%d %H:%M:%S"
        ),
        "commit": "unknown",
        **row,
    }
    # MAGI_ATTENTION_TELEMETRY=1: stamp the row with the run's comm /
    # balance context (tel_* columns) so a perf number carries the plan
    # that produced it. Empty dict (no extra columns) when off.
    from .. import telemetry

    full.update(
        {k: v for k, v in telemetry.flat_summary().items() if k not in full}
    )
    rows: list[dict] = []
    header: list[str] = []
    if os.path.exists(path):
        with open(path, newline="") as f:
            reader = csv.DictReader(f)
            header = list(reader.fieldnames or [])
            rows = list(reader)
    new_keys = [k for k in full if k not in header]
    if new_keys:
        header = header + new_keys
        with open(path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=header, restval="")
            w.writeheader()
            for r in rows:
                w.writerow(r)
            w.writerow(full)
    else:
        with open(path, "a", newline="") as f:
            csv.DictWriter(f, fieldnames=header, restval="").writerow(full)
    return path


def history_report(name: str, key_cols: list[str], value_col: str) -> str:
    """Latest row per config key with a delta vs the previous measurement.

    Returns a plain-text table (empty string when no history exists).
    """
    path = os.path.join(HISTORY_DIR, f"{name}.csv")
    if not os.path.exists(path):
        return ""
    with open(path, newline="") as f:
        rows = list(csv.DictReader(f))
    phase = value_col.split("_")[0]  # fwd_tflops -> fwd, fwdbwd_ms -> fwdbwd
    by_key: dict[tuple, list[dict]] = {}
    for r in rows:
        if r.get("suspect") or r.get(f"suspect_{phase}"):
            # harness marked the measurement unphysical (rate above the
            # chip ceiling even at the long-scan upper bound) — keep the
            # raw row in the CSV but never let it set a baseline. Plain
            # "suspect" taints the whole row; "suspect_<phase>" taints
            # only that phase's columns, so a bad fwd slope doesn't
            # suppress the same row's valid fwdbwd measurement.
            continue
        by_key.setdefault(tuple(r.get(k, "") for k in key_cols), []).append(r)
    lines = [
        f"# {name}: latest {value_col} per ({', '.join(key_cols)}) "
        f"with delta vs previous"
    ]
    for key, rs in sorted(by_key.items()):
        cur = rs[-1]
        try:
            val = float(cur.get(value_col) or "nan")
        except ValueError:
            continue
        delta = ""
        for prev in reversed(rs[:-1]):
            try:
                pv = float(prev.get(value_col) or "nan")
            except ValueError:
                continue
            if pv == pv and pv != 0:
                delta = f" ({(val - pv) / pv * 100:+.1f}% vs {prev['utc']})"
                break
        lines.append(
            f"{'/'.join(key)}: {value_col}={val:g} [{cur['utc']} "
            f"{cur.get('commit', '')}]{delta}"
        )
    return "\n".join(lines)
