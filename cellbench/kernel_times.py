"""Device time per FFA kernel body, read from the instruction names.

The program binds every Pallas call under ``jax.named_scope("magi" +
body.__name__)`` (``magiattention_tpu/kernels/_named.py``) and XLA names a
``tpu_custom_call`` instruction after the innermost scope, so a trace shows
``magi_fwd_kernel.1``, ``magi_delta_kernel.1``, ``magi_bwd_dq_kernel.1``,
``magi_bwd_dkv_kernel_gqa.1``. This is the one file of the benchmark that
knows that prefix and the bodies' names; ``event_classes.json`` still tells
forward from backward by the result type alone, and the two are checked
against each other: the backward bodies' times add up to ``ffa_bwd``'s, and
the named instructions' to the two FFA classes' (``ffa_bodies_sum_over_ffa``).

A body's name is looked for anywhere in the instruction's name, the longest
body first: where a kernel's scope is the outermost one, JAX decorates it
with the transform (``transpose_jvp_magi_bwd_dq_kernel__.3``).

Only instructions of the two FFA classes are read: a kernel that is not
FFA carries the prefix too (``magi_<body>``), is classed apart by a file of
``event_classes.d/`` and has readers of its own, and moves nothing here.

A program that names no instruction so (a commit before the names) has
nothing to read: every reader returns ``None`` and the metric is left out.
"""

from __future__ import annotations

PREFIX = "magi"
BODIES = {
    "fwd": ("_fwd_kernel_gqa", "_fwd_kernel"),
    "delta": ("_delta_kernel",),
    "bwd_dq": ("_bwd_dq_kernel_gqa", "_bwd_dq_kernel"),
    "bwd_dkv": ("_bwd_dkv_kernel_gqa", "_bwd_dkv_kernel"),
    "bwd_fused": ("_bwd_fused_kernel_gqa", "_bwd_fused_kernel"),
}
FFA_CLASSES = ["ffa_fwd", "ffa_bwd"]
_LONGEST_FIRST = sorted(
    ((PREFIX + body, kind) for kind, bodies in BODIES.items()
     for body in bodies),
    key=lambda pair: -len(pair[0]))


def instruction_name(label: str) -> str:
    """The instruction's name (XLA's instance number already off) of a
    ``DeviceTimes.ops`` key, ``"<class>:<name> <text>"``."""
    return label.split(":", 1)[1].split(" ", 1)[0]


def event_class(label: str) -> str:
    """The class of a ``DeviceTimes.ops`` key."""
    return label.split(":", 1)[0]


def kind_of(name: str) -> str | None:
    """Which kind of FFA body the instruction ``name`` is: a key of
    ``BODIES``, ``"other"`` for a name that carries the prefix and no body
    known here, ``None`` for an instruction that is not the library's."""
    for scope, kind in _LONGEST_FIRST:
        if scope in name:
            return kind
    return "other" if PREFIX + "_" in name else None


def ms_per_step_by_kind(trace) -> dict[str, float] | None:
    """``{kind: self milliseconds per step}`` of the named kernels of the
    FFA classes over the traced window, mean over the devices; every kind
    of ``BODIES`` and ``"other"`` is there (0.0 where none ran). ``None``
    without a trace, and where no such instruction carries the prefix."""
    if trace is None:
        return None
    ns = dict.fromkeys((*BODIES, "other"), 0.0)
    named = False
    for device in trace.devices.values():
        for label, self_ns in device.ops.items():
            if event_class(label) not in FFA_CLASSES:
                continue
            kind = kind_of(instruction_name(label))
            if kind is not None:
                named = True
                ns[kind] += self_ns
    if not named:
        return None
    scale = 1e-6 / len(trace.devices) / trace.steps
    return {kind: v * scale for kind, v in ns.items()}


def ms_per_step(ctx, kind: str) -> float | None:
    """One kind's time, for a metric's ``read(ctx)``."""
    times = ms_per_step_by_kind(ctx.trace)
    return None if times is None else times[kind]


def bodies_sum_over_ffa(ctx) -> float | None:
    """Time of every instruction of the two FFA classes that carries the
    prefix over the time of those classes, %: under 100 when a call site
    lost its name."""
    times = ms_per_step_by_kind(ctx.trace)
    if times is None:
        return None
    ffa = ctx.trace.self_ms_per_step(FFA_CLASSES)
    return 100.0 * sum(times.values()) / ffa if ffa else None
