"""From a profiler trace to per-layer device times.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain :class:`Event` lists (``jax.profiler.ProfileData``, nothing else): per
TPU its op line and its line of asynchronous operations, and the
benchmark's own host spans. Everything after that is arithmetic on those
lists, checked in ``tests/test_cellbench/test_trace_reduce.py`` against a
recorded chip trace.

What a v5e trace holds (looked at by hand, PR 22): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
program run), ``XLA Ops`` (every HLO instruction the core ran, one at a
time, named by its whole HLO text) and ``Async XLA Ops`` (one event per
asynchronous operation, from its ``*-start`` to its ``*-done``); and
``/host:CPU`` with a ``python`` line that carries ``TraceAnnotation`` spans.
An event's stats carry no JAX name stack, so an operation is known by its
instruction name, opcode, custom-call target and result type:
:func:`parse_hlo` cuts the HLO text down to those.

* The op line is sequential, so an event's **self time** is its interval
  minus that of events nested in it (none on a v5e today; control
  operations would be), self intervals are disjoint and their union is the
  device's busy time.
* Every event falls in exactly one **class** (``event_classes.d/*.json``,
  then ``event_classes.json``: the first pattern that matches ``"<name>
  <text>"``), so busy = the sum of the classes' self times and a step
  closes: classes + idle = window.
* A collective's time **in flight** is the union of its events on either
  line (the asynchronous line spans start to done); only its events on the
  op line keep the core from computing, so its **exposed** time is their
  self time there.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field

OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_BRACES = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(
    r"^%(?P<name>\S+) = (?P<type>\(.*?\)|\S+) (?P<opcode>[a-z][a-z0-9-]*)\(")
_DETAIL = re.compile(r'custom_call_target="([^"]+)"|kind=(k\w+)')


@dataclass(frozen=True)
class Event:
    name: str   # the HLO instruction's name, e.g. ``fusion.487``
    start: float  # nanoseconds on the trace's clock
    dur: float
    text: str = ""  # ``<opcode> [<target or fusion kind>] -> <result type>``

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    devices: dict[int, list[Event]] = field(default_factory=dict)
    async_ops: dict[int, list[Event]] = field(default_factory=dict)
    host: list[Event] = field(default_factory=list)


def parse_hlo(hlo: str) -> tuple[str, str]:
    """``(instruction name, "<opcode> [<detail>] -> <type>")`` of an op
    line event's name, layouts taken off; text that is no HLO instruction
    is returned as the name with an empty description."""
    flat = hlo
    while _BRACES.search(flat):
        flat = _BRACES.sub("", flat)
    m = _HLO.match(flat)
    if not m:
        return hlo, ""
    detail = _DETAIL.search(flat, m.end())
    extra = f" {detail.group(1) or detail.group(2)}" if detail else ""
    return m["name"], f"{m['opcode']}{extra} -> {m['type']}"


def load_xplane(path: str, host_spans: tuple[str, ...]) -> Trace:
    """Read both op lines of every TPU plane and the host spans named
    ``host_spans`` (the benchmark's own ``TraceAnnotation`` names)."""
    from jax.profiler import ProfileData

    def events(line):
        out = []
        for ev in line.events:
            name, text = parse_hlo(ev.name)
            out.append(Event(
                name, float(ev.start_ns), float(ev.duration_ns), text))
        return out

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OP_LINE:
                    trace.devices[int(m.group(1))] = events(line)
                elif line.name == ASYNC_LINE:
                    trace.async_ops[int(m.group(1))] = events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host.extend(
                    Event(ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events if ev.name in host_spans
                )
    trace.host.sort(key=lambda e: e.start)
    return trace


def save_events(trace: Trace, path: str) -> None:
    """The trace as JSON (the format of the recorded test trace)."""
    with open(path, "w", encoding="utf-8") as f:
        json.dump({
            "devices": {
                str(d): [[e.name, e.start, e.dur, e.text] for e in evs]
                for d, evs in trace.devices.items()
            },
            "async_ops": {
                str(d): [[e.name, e.start, e.dur, e.text] for e in evs]
                for d, evs in trace.async_ops.items()
            },
            "host": [[e.name, e.start, e.dur] for e in trace.host],
        }, f)


def load_events(path: str) -> Trace:
    with open(path, encoding="utf-8") as f:
        raw = json.load(f)
    def per_device(key):
        return {int(d): [Event(*e) for e in evs]
                for d, evs in raw.get(key, {}).items()}

    return Trace(per_device("devices"), per_device("async_ops"),
                 [Event(*e) for e in raw["host"]])


def _class_files(root: str | None) -> list[str]:
    """``event_classes.d/*.json`` in the order of their file names, then
    ``event_classes.json``; each looked for under ``root`` and under this
    checkout (as ``manifest.load_module`` looks), ``root``'s file standing
    in for one of the same name."""
    here = os.path.dirname(os.path.abspath(__file__))
    bases = [here] if root is None else [os.path.join(root, "cellbench"), here]
    extra = {
        os.path.basename(path): path for base in reversed(bases)
        for path in glob.glob(os.path.join(base, "event_classes.d", "*.json"))}
    last = next(
        path for base in bases
        if os.path.isfile(path := os.path.join(base, "event_classes.json")))
    return [extra[name] for name in sorted(extra)] + [last]


def load_classes(root: str | None = None) -> list[tuple[str, re.Pattern]]:
    """``[(class, compiled pattern)]``, first match wins: the classes of
    every ``event_classes.d/<name>.json`` (a file a later PR adds for a new
    kernel or collective, same format, in file-name order), then those of
    ``event_classes.json`` in file order. A file may name a class that is
    there already: its patterns are then tried earlier."""
    classes = []
    for path in _class_files(root):
        with open(path, encoding="utf-8") as f:
            classes += json.load(f)["classes"]
    return [
        (c["class"], re.compile("|".join(f"(?:{p})" for p in c["patterns"])))
        for c in classes
    ]


def classify(event: Event, classes) -> str:
    text = event.name + " " + event.text
    for cls, pattern in classes:
        if pattern.search(text):
            return cls
    raise ValueError(
        f"event {event.name!r} matches no class: the last class of "
        f"event_classes.json must match everything")


def clip(events: list[Event], t0: float, t1: float) -> list[Event]:
    """Events cut to the window ``[t0, t1)``; those outside are dropped."""
    out = []
    for e in events:
        s, t = max(e.start, t0), min(e.end, t1)
        if t > s:
            out.append(Event(e.name, s, t - s, e.text))
    return out


def self_intervals(events: list[Event]) -> list[tuple[Event, float]]:
    """``[(event, self nanoseconds)]`` on one sequential line: each event's
    duration minus that of the events nested directly inside it."""
    order = sorted(events, key=lambda e: (e.start, -e.dur))
    self_ns = [e.dur for e in order]
    stack: list[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e.dur
        stack.append(i)
    return [(e, max(s, 0.0)) for e, s in zip(order, self_ns)]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


@dataclass
class DeviceTimes:
    window_ns: float
    busy_ns: float
    self_ns: dict[str, float]          # class -> exposed (self) time
    in_flight_ns: dict[str, float]     # class -> time collectives were open
    ops: dict[str, float]              # "class:op" -> self time
    gaps: list[tuple[float, float]]    # idle (start, end), longest first
    has_async_line: bool               # the profiler wrote one for it

    @property
    def idle_ns(self) -> float:
        return self.window_ns - self.busy_ns


def _op_label(cls: str, event: Event) -> str:
    """A stable name for the breakdown: the class, the operation with XLA's
    instance number taken off (``fusion.12`` -> ``fusion``), and what it is
    (opcode, custom-call target or fusion kind, result type)."""
    name = re.sub(r"[.][0-9]+$", "", event.name)
    return f"{cls}:{name} {event.text}"[:120]


def reduce_device(
    events: list[Event], async_events: list[Event], classes,
    t0: float, t1: float,
) -> DeviceTimes:
    events = clip(events, t0, t1)
    self_ns: dict[str, float] = {cls: 0.0 for cls, _ in classes}
    by_class: dict[str, list[tuple[float, float]]] = {
        cls: [] for cls, _ in classes}
    ops: dict[str, float] = {}
    for e, s in self_intervals(events):
        cls = classify(e, classes)
        self_ns[cls] += s
        by_class[cls].append((e.start, e.end))
        label = _op_label(cls, e)
        ops[label] = ops.get(label, 0.0) + s
    for e in clip(async_events, t0, t1):
        by_class[classify(e, classes)].append((e.start, e.end))
    busy = union([(e.start, e.end) for e in events])
    busy_ns = sum(t - s for s, t in busy)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = sorted(
        ((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
         if edges[i + 1] > edges[i]),
        key=lambda g: g[0] - g[1],
    )
    return DeviceTimes(
        window_ns=t1 - t0,
        busy_ns=busy_ns,
        self_ns=self_ns,
        in_flight_ns={
            cls: sum(t - s for s, t in union(spans))
            for cls, spans in by_class.items()
        },
        ops=ops,
        gaps=gaps,
        has_async_line=bool(async_events),
    )


@dataclass
class Reduction:
    """What the metric readers see of a traced window of ``steps`` steps."""

    steps: int
    window_s: float
    devices: dict[int, DeviceTimes]
    host: list[Event]

    def _mean(self, pick) -> float:
        return sum(pick(d) for d in self.devices.values()) / len(self.devices)

    def busy_s(self) -> float:
        return self._mean(lambda d: d.busy_ns) * 1e-9

    def self_ms_per_step(self, classes: list[str]) -> float:
        """Exposed time of ``classes``, mean over devices, per step."""
        return self._mean(
            lambda d: sum(d.self_ns[c] for c in classes)) * 1e-6 / self.steps

    def classes(self) -> list[str]:
        return list(next(iter(self.devices.values())).self_ns)

    def _with_async_line(self) -> list[DeviceTimes]:
        """The devices whose asynchronous line was recorded (the profiler
        writes it for the first chip only), or all if none was."""
        seen = [d for d in self.devices.values() if d.has_async_line]
        return seen or list(self.devices.values())

    def in_flight_ms_per_step(self, classes: list[str]) -> float:
        """Time collectives of ``classes`` were under way, per step, mean
        over the devices that can show it."""
        seen = self._with_async_line()
        return sum(
            d.in_flight_ns[c] for d in seen for c in classes
        ) * 1e-6 / len(seen) / self.steps

    def exposed_share(self, classes: list[str]) -> float | None:
        """Exposed over in-flight time of ``classes`` on the devices that
        can show both; None if none ran."""
        seen = self._with_async_line()
        flight = sum(d.in_flight_ns[c] for d in seen for c in classes)
        exposed = sum(d.self_ns[c] for d in seen for c in classes)
        return exposed / flight if flight else None

    def idle_share(self) -> float:
        """Idle share of the window on the device that idles most."""
        return max(d.idle_ns / d.window_ns for d in self.devices.values())

    def idle_ms_per_step(self) -> float:
        return self._mean(lambda d: d.idle_ns) * 1e-6 / self.steps

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations with most self time, seconds, mean over
        devices, whole window."""
        total: dict[str, float] = {}
        for d in self.devices.values():
            for label, ns in d.ops.items():
                total[label] = total.get(label, 0.0) + ns
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / len(self.devices)] for k, v in top]

    def idle_by_host_span(self, n: int = 10) -> list[list]:
        """Idle seconds of the device that idles most, by the benchmark's
        host span open at the middle of each gap (``host:other`` where none
        is); the ``n`` largest."""
        worst = max(self.devices.values(), key=lambda d: d.idle_ns)
        total: dict[str, float] = {}
        for s, t in worst.gaps:
            mid = (s + t) / 2
            name = next(
                (h.name for h in self.host if h.start <= mid < h.end),
                "host:other")
            total[name] = total.get(name, 0.0) + (t - s)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]


def reduce_trace(trace: Trace, classes, steps: int) -> Reduction:
    """Reduce over the window the host spans cover (from the first span's
    start to the last one's end), or over the devices' own extent when the
    trace holds no host span."""
    if not trace.devices or not any(trace.devices.values()):
        raise ValueError("the trace holds no device operation")
    if trace.host:
        t0 = min(h.start for h in trace.host)
        t1 = max(h.end for h in trace.host)
    else:
        t0 = min(e.start for evs in trace.devices.values() for e in evs)
        t1 = max(e.end for evs in trace.devices.values() for e in evs)
    return Reduction(
        steps=steps,
        window_s=(t1 - t0) * 1e-9,
        devices={
            d: reduce_device(
                evs, trace.async_ops.get(d, []), classes, t0, t1)
            for d, evs in sorted(trace.devices.items())
        },
        host=trace.host,
    )
