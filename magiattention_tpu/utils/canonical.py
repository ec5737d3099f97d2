"""Canonical JSON forms: what a record is written as, and the one string a
decision or history key joins on across processes and restarts."""

from __future__ import annotations

import json
from typing import Any

import numpy as np


def jsonable(x: Any) -> Any:
    """Best-effort conversion to JSON-serializable builtins."""
    if isinstance(x, dict):
        return {str(k): jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [jsonable(v) for v in x]
    if isinstance(x, np.ndarray):
        return x.tolist()
    if isinstance(x, np.generic):
        return x.item()
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    return str(x)


def canonical_key(key: Any) -> str:
    """Stable string form of a decision/history key (dict keys sorted,
    tuples as lists) — the join key across processes and restarts."""
    return json.dumps(jsonable(key), sort_keys=True, separators=(",", ":"))
