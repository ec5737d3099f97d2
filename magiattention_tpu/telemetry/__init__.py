"""Runtime telemetry: per-plan / per-step metrics registry with JSONL export.

The observability counterpart of ``utils/profiling.py``: where profiling
puts *names* on the xprof timeline, telemetry records *numbers* — the
dispatch solver's balance ratio, every GroupCast stage's payload/wire/padding
rows and bytes, the FFA planner's padded-vs-true work, per-step host wall
times, and the runtime LRU's hit/miss/evict counts — as schema-versioned
JSONL records a CI job or ``scripts/telemetry_report.py`` can read back.

Gated on ``MAGI_ATTENTION_TELEMETRY`` (env/general.py typed getter, same
pattern as ``MAGI_ATTENTION_PROFILE_MODE``): with the flag off every entry
point here is a cheap early return — no file I/O, no timer reads, nothing
allocated (pinned by tests/test_support/test_telemetry.py).

Stage records carry the SAME scope names (``group_cast_stage0``,
``ffa_fwd_stage0``, ...) that ``utils/profiling.profile_scope`` annotates on
the xprof timeline, so a JSONL record links directly to its trace span when
both flags are on.
"""

from .registry import (  # noqa: F401
    SCHEMA_VERSION,
    TelemetryCollector,
    enabled,
    flat_summary,
    get_collector,
    inc,
    record_event,
    reset,
    set_gauge,
    stage_timer,
    summary,
)
from . import health  # noqa: F401
from .stats import band_area  # noqa: F401
from .store import (  # noqa: F401
    TelemetryStore,
    StoreState,
    store_active,
)
