"""Read the per-layer metrics of a run from its facts and its trace.

A metric is ``cellbench/metrics/<name>.json``; ``reader`` picks one of the
few general readers below, and a ``<name>.py`` beside it with ``read(ctx)``
takes over where data cannot say it. A reader that finds nothing to read
returns ``None`` and the metric is left out of the result line.

Readers: ``fact`` (a number the harness measured or counted: ``key``, and
``stat`` = ``value`` | ``median`` | ``max_over_mean`` for a list), ``ratio``
(``num`` / ``den`` of two facts), and on the device trace ``exposed_ms``
(self time of ``classes`` per step), ``in_flight_ms`` (time their
collectives were open per step), ``exposed_share`` (the first over the
second, %), ``idle_share`` (%).
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass

from cellbench.manifest import Cell, load_metric
from cellbench.trace_reduce import Reduction
from cellbench.traffic_gen import MaskSpec


@dataclass
class Context:
    """What a metric reader may look at."""

    cell: Cell
    family: object            # the cell's family_<family>.py (its counts)
    config: dict              # the configuration as run
    spec: MaskSpec            # the mask of the measured steps
    peaks: dict               # cellbench.peaks entry of the device
    facts: dict               # host-clock times and counts of this run
    trace: Reduction | None   # the reduced device trace (--trace 1 only)


def _fact(spec: dict, ctx: Context):
    value = ctx.facts.get(spec["key"])
    if value is None:
        return None
    stat = spec.get("stat", "value")
    if stat == "value":
        return float(value)
    if not value:
        return None
    if stat == "median":
        return float(statistics.median(value))
    if stat == "max_over_mean":
        return float(max(value) / statistics.fmean(value))
    raise ValueError(f"unknown stat {stat!r}")


def _ratio(spec: dict, ctx: Context):
    num, den = ctx.facts.get(spec["num"]), ctx.facts.get(spec["den"])
    return float(num / den) if num is not None and den else None


def _trace(reader: str, spec: dict, ctx: Context):
    if ctx.trace is None:
        return None
    if reader == "idle_share":
        return 100.0 * ctx.trace.idle_share()
    classes = spec["classes"]
    if reader == "exposed_ms":
        return ctx.trace.self_ms_per_step(classes)
    if reader == "in_flight_ms":
        return ctx.trace.in_flight_ms_per_step(classes)
    share = ctx.trace.exposed_share(classes)
    return None if share is None else 100.0 * share


def read_metric(root: str, name: str, ctx: Context) -> float | None:
    spec, read = load_metric(root, name)
    if read is not None:
        return read(ctx)
    reader = spec["reader"]
    if reader == "fact":
        return _fact(spec, ctx)
    if reader == "ratio":
        return _ratio(spec, ctx)
    if reader in ("exposed_ms", "in_flight_ms", "exposed_share", "idle_share"):
        return _trace(reader, spec, ctx)
    raise ValueError(f"metric {name!r}: unknown reader {reader!r}")
