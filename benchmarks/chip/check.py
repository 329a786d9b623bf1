"""The comparison that decides ``correct``.

A served result is one request's scores over its label tokens (the engine
renormalises the last position's logits over the label set). Against the
reference's logits of the same labels at the same position, each request
gives two numbers:

``logodds_err``
    max over labels of |served log-score - reference logit|, both centred
    on their mean over the labels: the error of the log-odds the user gets.
``label_gap``
    reference best label logit - reference logit of the label the system
    served: how far below the reference's choice the served choice lies
    (0 when both pick the same label).

Over the sample, each is read as its widest, and ``logodds_rms`` is the
root mean square of ``logodds_err``. A cell's limits name the numbers it
compares. With two labels a request's error is one signed number whose
size swings from request to request, so the widest of eight overlaps
between the program and a control one precision below; their root mean
square does not (``PERF.md``).

For the control, the same numbers are read from the control's logits in
place of the served scores (``label_gap`` then takes the label the control
puts first).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

NUMBERS = ("logodds_err", "label_gap", "logodds_rms")


def _centred(v: np.ndarray) -> np.ndarray:
    return v - v.mean()


def served_numbers(scores: Dict[int, float], served_label: int,
                   labels: Sequence[int], ref: np.ndarray) -> Dict[str, float]:
    """Both numbers for one served request (``ref``: reference logits of
    ``labels``)."""
    p = np.array([scores.get(int(t), 0.0) for t in labels], np.float64)
    if not np.all(p > 0) or not np.all(np.isfinite(p)):
        return {"logodds_err": math.inf, "label_gap": math.inf}
    err = float(np.max(np.abs(_centred(np.log(p)) - _centred(ref))))
    idx = list(labels).index(int(served_label)) if served_label in labels \
        else None
    gap = math.inf if idx is None else float(ref.max() - ref[idx])
    return {"logodds_err": err, "label_gap": gap}


def control_numbers(ctrl: np.ndarray, ref: np.ndarray) -> Dict[str, float]:
    """Both numbers for one request as the control computes it."""
    err = float(np.max(np.abs(_centred(ctrl) - _centred(ref))))
    return {"logodds_err": err,
            "label_gap": float(ref.max() - ref[int(np.argmax(ctrl))])}


def summary(rows: List[Dict[str, float]]) -> Dict[str, float]:
    """The numbers of a sample: the widest of each request's two, and the
    root mean square of ``logodds_err`` (inf for an empty sample)."""
    out = {k: max((r[k] for r in rows), default=math.inf)
           for k in ("logodds_err", "label_gap")}
    errs = [r["logodds_err"] for r in rows]
    out["logodds_rms"] = (math.sqrt(sum(e * e for e in errs) / len(errs))
                          if errs else math.inf)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            n_checked: int, failed: int) -> Dict[str, Dict]:
    """Each number the cell's limits name, beside its limit, plus the counts
    that also decide ``correct`` (no request may go unanswered, and some
    must be checked). A cell compares the numbers that separate its sound
    runs from its control."""
    out = {k: {"value": numbers[k], "limit": float(limits[k])}
           for k in NUMBERS if k in limits}
    if not out:
        raise ValueError("the cell's limits name no compared number")
    out["checked"] = {"value": n_checked, "limit": 1}
    out["failed"] = {"value": failed, "limit": 0}
    return out


def is_correct(v: Dict[str, Dict]) -> bool:
    ok = all(v[k]["value"] <= v[k]["limit"] for k in NUMBERS if k in v)
    return (ok and v["checked"]["value"] >= v["checked"]["limit"]
            and v["failed"]["value"] <= v["failed"]["limit"])


def sample(records: List[Dict], n: int, seed: int,
           keys: Sequence[str] = ("path", "instance")) -> List[Dict]:
    """Requests to compare, drawn from the seed: the longest, the longest of
    each step path and of each replica that served any, then random others
    up to ``n``."""
    rng = np.random.default_rng([int(seed), 99])
    by_len = sorted(records, key=lambda r: -r["n_input"])
    chosen: List[Dict] = by_len[:1]
    for key in keys:
        for value in sorted({r[key] for r in records}):
            best = next(r for r in by_len if r[key] == value)
            if best not in chosen:
                chosen.append(best)
    rest = [r for r in records if r not in chosen]
    for i in rng.permutation(len(rest))[:max(0, n - len(chosen))]:
        chosen.append(rest[int(i)])
    return chosen


def describe(v: Dict[str, Dict]) -> List[str]:
    return [f"check {k}: {d['value']!r} limit {d['limit']!r}"
            for k, d in v.items()]

