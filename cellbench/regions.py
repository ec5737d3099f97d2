"""Device time by the model's regions and by pass: the join of a traced
window with the compiled step's ``op_name``s.

A v5e trace knows an operation by its HLO instruction's name and nothing of
the scope it was traced under; the compiled program knows both. The program
hands out its own compiled text (``magiattention_tpu/utils/profiling.py``:
``compiled_step_texts``, from the signature ``_StepJit`` kept of its last
call under ``MAGI_ATTENTION_PROFILE_MODE``, which ``--trace 1`` sets) and
reads it into ``{instruction name: (scopes, pass)}``
(``instruction_scopes``). This file looks the window's events up in that
table: it finds the one ``.xplane.pb`` ``run.py`` left under
``.cellbench_trace/<cell>/``, loads it with the instruction names whole
(``trace_reduce.load_xplane``; ``DeviceTimes.ops`` drops their numbers),
clips it to the window of the host spans, takes self times and classes as
``trace_reduce`` does, and gives every event a region
(``profiling.MODEL_REGIONS``, ``DistAttnRuntime.calc_attn``, ``unscoped``
for an ``op_name`` outside every region, ``unnamed`` for an instruction
without one: a layout ``copy``, what the partitioner inserted) and a pass
(``fwd``, ``refwd``: ``jax.checkpoint``'s re-run of the forward, ``bwd``,
``none``). Once a process; the metrics' ``read(ctx)`` are calls into here.

Every reader returns ``None`` without a trace, without a device plane (the
CPU rehearsal), and where the program hands out no text or has no such
function (a commit before the regions): the metric is then left out of the
line. Beside the trace it leaves ``regions.json``: the window by region,
pass and class, what the join could not name, and what the join cost.
"""

from __future__ import annotations

import glob
import json
import os
import re
import time
from dataclasses import dataclass

from cellbench import manifest, trace_reduce

ATTN = "DistAttnRuntime.calc_attn"  # the span calc_attn has always had
UNSCOPED, UNNAMED = "unscoped", "unnamed"
KERNEL = "magi_"          # kernels/_named.py: every Pallas call's scope
SSD_KERNEL = "magi_ssd_"
OTHER, GROUP_COMM = "other_compute", "group_comm"
COVERED = 0.99  # of the window's instruction names, to take a text for it
_JOINED: list = []  # [(the Reduction it was made for, Joined | None)]


@dataclass(frozen=True)
class Row:
    """One event of the window: its self time, class, region and pass."""

    name: str    # the instruction's name, number and all
    text: str    # ``<opcode> [<detail>] -> <type>``
    cls: str
    region: str  # a region's name, ``unscoped`` or ``unnamed``
    which: str   # the pass
    scopes: tuple
    ns: float
    boundary: bool  # a fusion whose instructions name more than one region


@dataclass
class Joined:
    rows: list[Row]     # every device's
    devices: int
    steps: int
    busy_ns: float      # summed over the devices
    regions: frozenset  # the regions the compiled program names at all
    facts: dict         # what the join cost and covered

    def ms_per_step(self, pick) -> float:
        """Self milliseconds per step of the rows ``pick`` takes, mean over
        the devices (as ``Reduction.self_ms_per_step``)."""
        return sum(r.ns for r in self.rows if pick(r)) * 1e-6 / (
            self.devices * self.steps)

    def share_of_busy(self, pick) -> float:
        return 100.0 * sum(r.ns for r in self.rows if pick(r)) / self.busy_ns


def find_xplane(cell_name: str) -> str | None:
    """The one ``.xplane.pb`` of the cell's traced run, or ``None``."""
    found = glob.glob(os.path.join(
        manifest.ROOT, ".cellbench_trace", cell_name, "plugins", "profile",
        "*", "*.xplane.pb"))
    return found[0] if len(found) == 1 else None


def pick_table(tables: dict, names: set):
    """Of ``{step: (table, on_boundary)}`` the one entry whose instruction
    names cover the window's ``names``; ``None`` where none does or two do."""
    covering = [
        (step, pair) for step, pair in tables.items()
        if len(names & pair[0].keys()) >= COVERED * len(names)]
    return covering[0] if len(covering) == 1 else None


def join(trace: trace_reduce.Trace, window, classes, tables: dict,
         steps: int, region_of) -> Joined | None:
    """The events of ``trace`` inside ``window`` ``(t0, t1)``, each with its
    class (``classes``), and its region (``region_of(scopes)``, the
    program's ``profiling.region_of``) and pass from the one table of
    ``tables`` that knows them (:func:`pick_table`)."""
    per_device = {
        d: trace_reduce.self_intervals(trace_reduce.clip(evs, *window))
        for d, evs in trace.devices.items()}
    names = {e.name for pairs in per_device.values() for e, _ in pairs}
    picked = pick_table(tables, names) if names else None
    if picked is None:
        return None
    step, (table, on_boundary) = picked
    rows, busy_ns = [], 0.0
    for pairs in per_device.values():
        busy_ns += sum(t - s for s, t in trace_reduce.union(
            [(e.start, e.end) for e, _ in pairs]))
        for e, ns in pairs:
            scopes, which = table.get(e.name, (None, "none"))
            region = UNNAMED if scopes is None else (
                region_of(scopes) or UNSCOPED)
            rows.append(Row(
                e.name, e.text, trace_reduce.classify(e, classes), region,
                which, scopes or (), ns, on_boundary.get(e.name, False)))
    return Joined(
        rows, len(per_device), steps, busy_ns,
        frozenset(region_of(s) for s, _ in table.values()) - {None},
        {"step": step, "names_covered": len(names & table.keys()) / len(names)})


def joined(ctx) -> Joined | None:
    """The join of this run's traced window, made once a process."""
    if ctx.trace is None:
        return None
    if not (_JOINED and _JOINED[0][0] is ctx.trace):
        _JOINED[:] = [(ctx.trace, _join_run(ctx))]
    return _JOINED[0][1]


def _join_run(ctx) -> Joined | None:
    try:
        from magiattention_tpu.utils import profiling

        texts_of, scopes_of = (
            profiling.compiled_step_texts, profiling.instruction_scopes)
    except (ImportError, AttributeError):
        return None  # a program from before the regions
    path = find_xplane(ctx.cell.name)
    if path is None:
        return None
    trace = trace_reduce.load_xplane(path, ())
    if not trace.devices or not any(trace.devices.values()):
        return None
    if ctx.trace.host:
        window = (min(h.start for h in ctx.trace.host),
                  max(h.end for h in ctx.trace.host))
    else:  # as trace_reduce.reduce_trace: the devices' own extent
        window = (
            min(e.start for evs in trace.devices.values() for e in evs),
            max(e.end for evs in trace.devices.values() for e in evs))
    t0 = time.perf_counter()
    texts = texts_of()
    t1 = time.perf_counter()
    if not texts:
        return None
    tables = {step: scopes_of(text) for step, text in texts.items()}
    out = join(trace, window, trace_reduce.load_classes(), tables,
               ctx.trace.steps, profiling.region_of)
    if out is not None:
        out.facts.update(
            compiled_step_texts_s=t1 - t0,
            text_bytes={step: len(text) for step, text in texts.items()},
            join_s=time.perf_counter() - t1)
        with open(os.path.join(os.path.dirname(path), "regions.json"), "w",
                  encoding="utf-8") as f:
            json.dump(report(out, ctx.cell.name), f, indent=1)
    return out


# -- what the metrics read -----------------------------------------------------


def in_closure(row: Row) -> bool:
    """An instruction the table knows that lies in a model region, in
    ``DistAttnRuntime.calc_attn`` or in a ``magi_*`` kernel."""
    return row.region != UNNAMED and (
        row.region != UNSCOPED or KERNEL in row.name)


def regions_sum_over_busy(ctx) -> float | None:
    j = joined(ctx)
    return None if j is None else j.share_of_busy(in_closure)


def remat_recompute_share(ctx) -> float | None:
    j = joined(ctx)
    return None if j is None else j.share_of_busy(
        lambda r: r.which == "refwd")


def region_ms_per_step(ctx, *regions: str) -> float | None:
    """Self time per step in ``regions``, every class and pass; ``None``
    where the compiled program names none of them."""
    j = joined(ctx)
    if j is None or not j.regions & set(regions):
        return None
    return j.ms_per_step(lambda r: r.region in regions)


def glue_ms_per_step(ctx, region: str, kernel: str = KERNEL) -> float | None:
    """Self time per step of class ``other_compute`` in ``region`` less the
    instructions named after ``kernel``: what stands round the kernels."""
    j = joined(ctx)
    if j is None or region not in j.regions:
        return None
    return j.ms_per_step(
        lambda r: r.region == region and r.cls == OTHER
        and kernel not in r.name)


def is_reduce(row: Row) -> bool:
    """A cast's transpose IS a reduce: an event under a ``group_reduce*``
    scope, or of pass ``bwd`` under ``group_cast*`` alone."""
    if any(s.startswith("group_reduce") for s in row.scopes):
        return True
    return row.which == "bwd" and any(
        s.startswith("group_cast") for s in row.scopes)


def group_comm_ms_per_step(ctx, kind: str) -> float | None:
    """Exposed time per step of class ``group_comm``: ``"reduce"`` by
    :func:`is_reduce`, ``"cast"`` the rest (passes ``fwd`` and ``refwd``)."""
    j = joined(ctx)
    if j is None or not any(r.cls == GROUP_COMM for r in j.rows):
        return None
    return j.ms_per_step(
        lambda r: r.cls == GROUP_COMM and is_reduce(r) == (kind == "reduce"))


# -- the report beside the trace -----------------------------------------------


def _top(j: Joined, pick, n: int, numbered: bool = True) -> list[list]:
    """The ``n`` largest ``[label, ms per step]`` of the rows ``pick`` takes,
    one an instruction, or with ``numbered`` off one a kind of instruction
    (XLA's instance number taken off, as ``trace_reduce`` labels them)."""
    total: dict[str, float] = {}
    for r in j.rows:
        if pick(r):
            name = r.name if numbered else re.sub(r"[.][0-9]+$", "", r.name)
            label = f"{r.region}:{r.which}:{r.cls}:{name} {r.text}"[:160]
            total[label] = total.get(label, 0.0) + r.ns
    scale = 1e-6 / (j.devices * j.steps)
    return [[k, v * scale] for k, v in sorted(
        total.items(), key=lambda kv: -kv[1])[:n]]


def report(j: Joined, cell: str) -> dict:
    """Milliseconds per step by region, pass and class; class
    ``other_compute`` over regions, ``unscoped`` and ``unnamed`` and its
    part in fusions that name two regions; the largest operations outside
    the closure and, a region, its largest kinds of operation; the group
    collectives that carry no ``group_*`` scope."""
    by: dict[str, dict[str, dict[str, float]]] = {}
    for r in j.rows:
        cell_ = by.setdefault(r.region, {}).setdefault(r.which, {})
        cell_[r.cls] = cell_.get(r.cls, 0.0) + r.ns
    scale = 1e-6 / (j.devices * j.steps)
    other = {
        region: sum(classes.get(OTHER, 0.0) for classes in passes.values())
        * scale for region, passes in by.items()}
    other = {region: ms for region, ms in other.items() if ms}
    return {
        "cell": cell, **j.facts,
        "busy_ms_per_step": j.busy_ns * scale,
        "regions_sum_over_busy": j.share_of_busy(in_closure),
        "ms_per_step": {
            region: {which: {c: ns * scale for c, ns in classes.items()}
                     for which, classes in passes.items()}
            for region, passes in sorted(by.items())},
        "other_compute_ms_per_step": {
            "by_region": other, "sum": sum(other.values()),
            "on_boundary": j.ms_per_step(
                lambda r: r.cls == OTHER and r.boundary)},
        "group_comm_without_scope_ms_per_step": j.ms_per_step(
            lambda r: r.cls == GROUP_COMM and not any(
                s.startswith(("group_cast", "group_reduce"))
                for s in r.scopes)),
        "outside_closure": _top(j, lambda r: not in_closure(r), 40),
        "largest": _top(j, lambda r: True, 40),
        "largest_kinds_by_region": {
            region: _top(j, lambda r, region=region: r.region == region, 12,
                         numbered=False)
            for region in sorted(by)},
    }
