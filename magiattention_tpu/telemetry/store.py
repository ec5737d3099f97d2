"""Persistent cross-run telemetry store: run history and the resilience
layer's memory.

Where telemetry/registry.py streams write-only per-process JSONL, this
module keeps a small *readable* history that survives restarts and is
shared by every process pointing at the same directory:

- ``history-<host>-<pid>-<token>.jsonl`` — append-only rows, one writer
  per file, each line written atomically (O_APPEND, single write; see
  telemetry/export.py). Safe for any number of concurrent writers.
- ``store.json`` — compacted snapshot, replaced atomically via a temp
  file + ``os.replace``. :meth:`TelemetryStore.compact` folds all history
  files into it; run compaction when no writers are active (end of run,
  CI, or the report tool) — a writer whose open file is deleted under it
  loses subsequent rows.

Row kinds (``rk`` field):

- ``hist``        — aggregated ``attn_step`` / ``serve_step`` /
  ``plan_solve`` / ``step_retry`` run history keyed by (mask-class
  signature, shape, dtype, mesh, env snapshot signature), fed by
  :func:`ingest_event` from the collector.
- ``rank_health`` — the straggler monitor's per-rank observations
  (telemetry/health.py).
- ``quarantine``  — a backend the step watchdog quarantined for a decision
  key (resilience/watchdog.py), so restarts remember.

The store records and reports; nothing that chooses a kernel, a tile or a
solver constant reads it. A row of any other kind — a directory written
by a build that still kept ``measure`` / ``policy`` / ``obs`` / ``calib``
/ ``drift`` rows — is skipped on load.

Everything here is gated on :func:`store_active` — with
``MAGI_ATTENTION_TELEMETRY`` off every entry point is a cheap early
return: no file I/O, no state.
"""

from __future__ import annotations

import glob
import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any

from ..env import backend as env_backend
from ..env import general as env_general
from ..utils.canonical import canonical_key, jsonable
from .export import JsonlSink, process_unique_path

STORE_SCHEMA_VERSION = 1
SNAPSHOT_NAME = "store.json"
HISTORY_PREFIX = "history"

# collector kinds ingest_event aggregates into run history
_HISTORY_KINDS = ("attn_step", "serve_step", "plan_solve", "step_retry")
# attn_step fields forming the run-history key (ISSUE: mask-class
# signature, shape, dtype, mesh, env snapshot)
_ATTN_KEY_FIELDS = (
    "mask_sig", "q_shape", "kv_shape", "dtype", "mesh_sig", "env_sig",
    "cp_size",
)


def store_active() -> bool:
    """The ONE gate every store entry point checks first."""
    return env_general.is_telemetry_enable()


@dataclass
class StoreState:
    """In-memory aggregate view of the store (snapshot + replayed rows)."""

    history: dict[str, dict[str, Any]] = field(default_factory=dict)
    rank_health: dict[str, dict[str, Any]] = field(default_factory=dict)
    quarantine: dict[str, dict[str, Any]] = field(default_factory=dict)


def _apply(state: StoreState, row: dict[str, Any]) -> None:
    """Fold one history row into the aggregate state."""
    rk = row.get("rk")
    if rk == "hist":
        hkey = f"{row['kind']}|{row['key']}"
        h = state.history.setdefault(
            hkey,
            {
                "kind": row["kind"],
                "count": 0,
                "wall_ms_sum": 0.0,
                "wall_ms_min": None,
                "wall_ms_max": None,
            },
        )
        h["count"] += 1
        ms = row.get("wall_ms")
        if ms is not None:
            ms = float(ms)
            h["wall_ms_sum"] += ms
            if h["wall_ms_min"] is None or ms < h["wall_ms_min"]:
                h["wall_ms_min"] = ms
            if h["wall_ms_max"] is None or ms > h["wall_ms_max"]:
                h["wall_ms_max"] = ms
        h["last_ts"] = row.get("ts")
    elif rk == "rank_health":
        r = str(row.get("rank"))
        h = state.rank_health.setdefault(
            r,
            {
                "count": 0,
                "transitions": 0,
                "ewma_ms": None,
                "capacity": 1.0,
                "degraded": False,
            },
        )
        h["count"] += 1
        if row.get("ewma_ms") is not None:
            h["ewma_ms"] = float(row["ewma_ms"])
        if row.get("capacity") is not None:
            cap = float(row["capacity"])
            if cap != h["capacity"]:
                h["transitions"] += 1
            h["capacity"] = cap
        h["degraded"] = bool(row.get("degraded", False))
        h["last_ts"] = row.get("ts")
    elif rk == "quarantine":
        qkey = f"{row.get('decision')}|{row.get('key')}|{row.get('backend')}"
        if row.get("action") == "clear":
            state.quarantine.pop(qkey, None)
        else:
            q = state.quarantine.setdefault(
                qkey,
                {
                    "decision": row.get("decision"),
                    "key": row.get("key"),
                    "backend": row.get("backend"),
                    "trips": 0,
                },
            )
            q["trips"] = max(q["trips"], int(row.get("trips", 1)))
            q["last_ts"] = row.get("ts")
    # any other rk (a newer build's, or the measure / policy / obs / calib /
    # drift rows an older build wrote): skipped


def _load_from_disk(directory: str) -> StoreState:
    state = StoreState()
    snap_path = os.path.join(directory, SNAPSHOT_NAME)
    try:
        with open(snap_path) as f:
            snap = json.load(f)
        if isinstance(snap, dict) and snap.get("v", 0) <= STORE_SCHEMA_VERSION:
            state.history = snap.get("history", {})
            state.rank_health = snap.get("rank_health", {})
            state.quarantine = snap.get("quarantine", {})
    except (OSError, ValueError):
        pass  # no/garbled snapshot: rebuild from history alone
    for path in sorted(glob.glob(os.path.join(directory, f"{HISTORY_PREFIX}-*.jsonl"))):
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        row = json.loads(line)
                    except ValueError:
                        continue  # torn/foreign line: skip, keep reading
                    if row.get("v", 0) > STORE_SCHEMA_VERSION:
                        continue
                    _apply(state, row)
        except OSError:
            continue
    return state


class TelemetryStore:
    """One process's handle on a store directory: appends rows to its own
    history file (line-atomic) and keeps the aggregate state in memory."""

    def __init__(self, directory: str) -> None:
        self.directory = directory
        self._lock = threading.Lock()
        self._sink = JsonlSink(process_unique_path(directory, HISTORY_PREFIX))
        self._state: StoreState | None = None

    # -- persistence ------------------------------------------------------

    def _append(self, row: dict[str, Any]) -> None:
        """Write one row (caller holds the lock) and fold it into the
        in-memory state so this process sees its own writes immediately."""
        row.setdefault("v", STORE_SCHEMA_VERSION)
        row.setdefault("ts", time.time())
        self._sink.write(row)
        _apply(self._ensure_loaded(), row)

    def _ensure_loaded(self) -> StoreState:
        if self._state is None:
            self._state = _load_from_disk(self.directory)
        return self._state

    def load(self) -> StoreState:
        """(Re)load the aggregate state from disk: snapshot + every
        history file, including other writers'."""
        with self._lock:
            self._state = _load_from_disk(self.directory)
            return self._state

    def compact(self) -> str:
        """Fold all history files into ``store.json`` (atomic replace) and
        delete them. Call with no concurrent writers; this process's own
        file is rotated so it keeps appending safely afterwards."""
        with self._lock:
            self._sink.close()
            files = sorted(
                glob.glob(
                    os.path.join(self.directory, f"{HISTORY_PREFIX}-*.jsonl")
                )
            )
            state = _load_from_disk(self.directory)
            snap_path = os.path.join(self.directory, SNAPSHOT_NAME)
            tmp_path = snap_path + f".tmp-{os.getpid()}"
            os.makedirs(self.directory, exist_ok=True)
            with open(tmp_path, "w") as f:
                json.dump(
                    {
                        "v": STORE_SCHEMA_VERSION,
                        "history": state.history,
                        "rank_health": state.rank_health,
                        "quarantine": state.quarantine,
                    },
                    f,
                )
            os.replace(tmp_path, snap_path)
            for path in files:
                try:
                    os.unlink(path)
                except OSError:
                    pass
            self._sink = JsonlSink(
                process_unique_path(self.directory, HISTORY_PREFIX)
            )
            self._state = state
            return snap_path

    def close(self) -> None:
        with self._lock:
            self._sink.close()

    # -- writers ----------------------------------------------------------

    def record_history(
        self, kind: str, key: Any, wall_ms: float | None, **extra: Any
    ) -> None:
        with self._lock:
            row: dict[str, Any] = {
                "rk": "hist",
                "kind": kind,
                "key": canonical_key(key),
            }
            if wall_ms is not None:
                row["wall_ms"] = float(wall_ms)
            if extra:
                row["ctx"] = jsonable(extra)
            self._append(row)

    def record_rank_health(
        self,
        rank: int,
        wall_ms: float | None,
        ewma_ms: float | None,
        capacity: float,
        degraded: bool,
        **extra: Any,
    ) -> None:
        with self._lock:
            row: dict[str, Any] = {
                "rk": "rank_health",
                "rank": int(rank),
                "capacity": float(capacity),
                "degraded": bool(degraded),
            }
            if wall_ms is not None:
                row["wall_ms"] = float(wall_ms)
            if ewma_ms is not None:
                row["ewma_ms"] = float(ewma_ms)
            if extra:
                row["ctx"] = jsonable(extra)
            self._append(row)

    def record_quarantine(
        self,
        decision: str,
        key: Any,
        backend: str,
        trips: int,
        action: str = "add",
    ) -> None:
        with self._lock:
            self._append(
                {
                    "rk": "quarantine",
                    "decision": decision,
                    "key": canonical_key(key),
                    "backend": backend,
                    "trips": int(trips),
                    "action": action,
                }
            )

    # -- readers ----------------------------------------------------------

    def rank_health_view(self) -> dict[str, dict[str, Any]]:
        with self._lock:
            return {
                r: dict(h)
                for r, h in self._ensure_loaded().rank_health.items()
            }

    def quarantined(self, decision: str, key: Any) -> set[str]:
        """Backends quarantined for a decision key (restart-persistent)."""
        prefix = f"{decision}|{canonical_key(key)}|"
        with self._lock:
            return {
                q["backend"]
                for qkey, q in self._ensure_loaded().quarantine.items()
                if qkey.startswith(prefix)
            }


# -- module-level gated access (what the watchdog and the collector use) ----

_store: TelemetryStore | None = None
_store_lock = threading.Lock()


def resolve_store_dir() -> str:
    d = env_backend.store_dir()
    return d or os.path.join(env_general.telemetry_dir(), "store")


def get_store() -> TelemetryStore | None:
    """The process-global store, or None when inactive. Recreated when the
    resolved directory changes (tests redirect via env)."""
    if not store_active():
        return None
    global _store
    directory = resolve_store_dir()
    with _store_lock:
        if _store is None or _store.directory != directory:
            if _store is not None:
                _store.close()
            _store = TelemetryStore(directory)
        return _store


def reset() -> None:
    """Drop the global store (tests; recreated on demand)."""
    global _store
    with _store_lock:
        if _store is not None:
            _store.close()
        _store = None


def quarantined_backends(decision: str, key: Any) -> set[str]:
    """Restart-persistent quarantine set for a decision key; empty when
    the store is inactive (quarantine still works in-process then)."""
    st = get_store()
    return set() if st is None else st.quarantined(decision, key)


def record_quarantine(
    decision: str, key: Any, backend: str, trips: int, action: str = "add"
) -> None:
    st = get_store()
    if st is not None:
        st.record_quarantine(decision, key, backend, trips, action=action)


# -- collector ingest -------------------------------------------------------


def ingest_event(record: dict[str, Any]) -> None:
    """Collector hook: fold a telemetry record into the persistent store.
    Called for every record the collector writes; cheap kind/gate check
    first so non-store kinds cost one tuple membership test."""
    kind = record.get("kind")
    if kind not in _HISTORY_KINDS and kind != "rank_health":
        return
    st = get_store()
    if st is None:
        return
    wall_ms = record.get("wall_ms")
    if kind == "attn_step":
        key = {f: record.get(f) for f in _ATTN_KEY_FIELDS}
        st.record_history("attn_step", key, wall_ms)
    elif kind == "serve_step":
        key = {
            "occupancy": record.get("occupancy"),
            "pages_in_use": record.get("pages_in_use"),
        }
        st.record_history("serve_step", key, wall_ms)
    elif kind == "plan_solve":
        key = {
            k: record.get(k)
            for k in ("signature", "cp_size", "num_slices", "planner")
            if k in record
        }
        st.record_history("plan_solve", key, wall_ms)
    elif kind == "rank_health":
        st.record_rank_health(
            rank=int(record.get("rank", -1)),
            wall_ms=record.get("wall_ms"),
            ewma_ms=record.get("ewma_ms"),
            capacity=float(record.get("capacity", 1.0)),
            degraded=bool(record.get("degraded", False)),
        )
    elif kind == "step_retry":
        key = {
            k: record.get(k)
            for k in ("stage", "from_backend", "to_backend", "error")
            if k in record
        }
        st.record_history("step_retry", key, wall_ms)
