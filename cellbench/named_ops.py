"""Device time of operations picked by their instruction names.

``event_classes.json`` and ``event_classes.d/`` put every device operation in
one class, and ``tests/test_cellbench`` pins a checkout's class names; a
kernel that has no class of its own (``event_classes.d/60-kernels-by-name.json``
sends the scan's calls and the grouped products to ``other_compute``) is
still a named instruction of the trace: ``DeviceTimes.ops`` keeps the self
time of every operation under ``"<class>:<name> <text>"``. A metric's
``read(ctx)`` sums the names it wants, as ``kernel_times.py`` does for the
FFA bodies.
"""

from __future__ import annotations

import re

from cellbench import kernel_times

SSD_FWD = r"magi_ssd_fwd_kernel"
SSD_BWD = r"magi_ssd_bwd_kernel"
GROUPED = r"ragged[-_]dot"


def ms_per_step(trace, *patterns: str) -> float | None:
    """Self milliseconds per step of the operations whose instruction name
    holds one of ``patterns`` (regular expressions), mean over the devices;
    ``None`` without a trace and where no operation is named so (a program
    that lacks the kernel: the metric is left out of the line)."""
    if trace is None:
        return None
    wanted = re.compile("|".join(f"(?:{p})" for p in patterns))
    ns, found = 0.0, False
    for device in trace.devices.values():
        for label, self_ns in device.ops.items():
            if wanted.search(kernel_times.instruction_name(label)):
                ns, found = ns + self_ns, True
    if not found:
        return None
    return ns * 1e-6 / len(trace.devices) / trace.steps
