"""The comparison that decides ``correct``.

Every answer of the run is compared: each request's logits, as they came
back through the front door, against the plain float32 reference's
logits of the same pool images.  Three numbers, each with its limit from
the configuration's file (``check``):

* ``worst_row_rel_l2`` — the largest relative L2 error of one image's
  logits, ``|served - ref| / |ref|``, over every image served;
* ``rel_l2`` — the same over all served images together;
* ``unanswered`` — requests never answered, or answered with a logit that
  is not finite.

A number passes when it is at most its limit.  A limit that is not set
yet passes nothing.
"""
from __future__ import annotations

import numpy as np


def compare(records, ref_logits: np.ndarray) -> dict:
    num = den = 0.0
    worst = 0.0
    unanswered = 0
    for r in records:
        got = getattr(r.req, "logits", None)
        if not r.done or got is None:
            unanswered += 1
            continue
        got = np.asarray(got, np.float64)
        want = np.asarray(ref_logits[r.off:r.off + r.n], np.float64)
        if got.shape != want.shape or not np.isfinite(got).all():
            unanswered += 1
            continue
        e2 = np.sum((got - want) ** 2, axis=-1)
        w2 = np.sum(want ** 2, axis=-1)
        num += float(e2.sum())
        den += float(w2.sum())
        worst = max(worst, float(np.max(np.sqrt(e2 / w2))))
    rel = float(np.sqrt(num / den)) if den > 0 else float("nan")
    return {"worst_row_rel_l2": worst, "rel_l2": rel,
            "unanswered": unanswered}


def judge(numbers: dict, checks: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the numbers the
    configuration names under ``check``."""
    out, ok = {}, True
    for name, spec in checks.items():
        v, lim = numbers[name], spec["limit"]
        passed = lim is not None and np.isfinite(v) and v <= lim
        ok = ok and bool(passed)
        out[name] = {"value": v, "limit": lim}
    return ok, out
