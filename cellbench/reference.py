"""The comparison that decides ``correct``, whatever the block.

A family file (``family_<family>.py``) gives the harness a plain reference
of its block, a function ``(params, cfg, tokens, labels, spec) -> {name:
value}``, a program of its own that returns the same names from the system
under test, and ``CHECKS``: the names compared, each ``{"kind", "tol",
"why"}``. This file knows the kinds and nothing of any block:

* ``abs_per_sqrt_targets``: ``|system - reference|`` of a scalar against
  ``tol / sqrt(targets)``, ``targets`` the positions that have a label (a
  mean over n tokens of errors of either sign shrinks as its root);
* ``rel_frobenius``: ``|system - reference|_F / |reference|_F`` of an array
  against ``tol``.

A NaN anywhere fails: it compares false.
"""

from __future__ import annotations

import numpy as np

KINDS = ("abs_per_sqrt_targets", "rel_frobenius")


def rel_err(got, ref) -> float:
    """Relative Frobenius error on the host, sums in float64."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)

    def norm(x):
        return float(np.sqrt(np.sum(np.square(x), dtype=np.float64)))

    return norm(got - ref) / norm(ref)


def compare(
    system: dict, ref: dict, checks: dict[str, dict], targets: int
) -> dict[str, dict]:
    """``{name: {"err", "tol", "ok"}}`` for every name of ``checks``, read
    from ``system`` and ``ref``; a name either lacks is an error."""
    out = {}
    for name, check in checks.items():
        for side, values in (("program", system), ("reference", ref)):
            if name not in values:
                raise KeyError(
                    f"check {name!r}: the family's {side} returned only "
                    f"{sorted(values)}")
        kind, tol = check["kind"], check["tol"]
        if kind == "abs_per_sqrt_targets":
            err = abs(float(system[name]) - float(ref[name]))
            tol = tol / float(np.sqrt(targets))
        elif kind == "rel_frobenius":
            err = rel_err(system[name], ref[name])
        else:
            raise ValueError(
                f"check {name!r}: unknown kind {kind!r} (known: {KINDS})")
        out[name] = {"err": err, "tol": tol, "ok": bool(err <= tol)}
    return out
