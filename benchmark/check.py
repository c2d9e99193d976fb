"""The comparison that decides ``correct``: the program's served
distributions against the plain reference's logits, row by row.

Each row's reading is the root-mean-square difference of log-probability
over the reference's ``TOP_K`` most likely tokens. A row either sits on the
floor that rounding alone leaves, or above it by what the expert choices that
parted at a near-tie on its way cost (PERF.md, Findings, has the look). The
limits file ``limits/<workload>.json`` names the numbers held, each with its
limit (how they were set is in PERF.md):

``row_rms_q10``     the row a tenth of the way up: the floor. A lower precision
                    lifts every row, the best ones too, so the control (the
                    program with its own int8 path on) fails it.
``row_rms_median``  the median row: errors of moderate size in half the rows.
``row_rms_max``     the worst row. A row whose answer was altered reads
                    several times what any sound row does.

Beside them, unheld (limit null): ``logp_rms``, the root-mean-square over all
rows, and ``pick_gap``, the widest gap by which the program's first token
lies below the reference's best (both swing with near-ties).
"""

from __future__ import annotations

import numpy as np

TOP_K = 100
STATS = ("row_rms_q10", "row_rms_median", "row_rms_max", "logp_rms", "pick_gap")


def log_softmax(logits: np.ndarray) -> np.ndarray:
    x = np.asarray(logits, np.float64)
    x = x - x.max(axis=-1, keepdims=True)
    return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))


def row_diffs(prog_probs: np.ndarray, ref_logits: np.ndarray):
    """Per row: (logp difference over the reference's top tokens [R, TOP_K],
    gap of the program's pick [R]). Inputs [R, V] each."""
    p = np.asarray(prog_probs, np.float64)
    if p.shape != np.shape(ref_logits) or not np.isfinite(p).all():
        return np.full((len(p), TOP_K), np.inf), np.full(len(p), np.inf)
    ref = log_softmax(ref_logits)
    top = np.argsort(-ref, axis=-1)[:, :TOP_K]
    with np.errstate(divide="ignore"):
        lp = np.log(np.take_along_axis(p, top, -1))
    pick = np.take_along_axis(ref, p.argmax(-1)[:, None], -1)[:, 0]
    return lp - np.take_along_axis(ref, top, -1), ref.max(-1) - pick


def summarize(d: np.ndarray, gap: np.ndarray) -> dict:
    """The numbers of one comparison from its rows' differences."""
    if not len(d):
        return {k: float("inf") for k in STATS} | {"rows": 0}
    msq = (d * d).mean(axis=-1)
    rms = np.sqrt(msq)
    return {
        "logp_rms": float(np.sqrt(msq.mean())),
        "pick_gap": float(gap.max()),
        "rows": int(len(rms)),
        "row_rms_max": float(rms.max()),
        "row_rms_median": float(np.median(rms)),
        "row_rms_q10": float(np.quantile(rms, 0.1)),
    }


def diffs(prog_probs: list[np.ndarray], ref_logits: list[np.ndarray]):
    """``row_diffs`` over all rows of all sampled answers."""
    pairs = [row_diffs(p, l) for p, l in zip(prog_probs, ref_logits)]
    if not pairs:
        return np.zeros((0, TOP_K)), np.zeros(0)
    return np.concatenate([d for d, _ in pairs]), np.concatenate([g for _, g in pairs])


def compare(prog_probs: list[np.ndarray], ref_logits: list[np.ndarray]) -> dict:
    return summarize(*diffs(prog_probs, ref_logits))


def softmax(logits: np.ndarray) -> np.ndarray:
    return np.exp(log_softmax(logits))


def verdict(numbers: dict, limits: dict, rows_min: int = 1) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}). The limits file names the held
    numbers: each has to be at or under its limit, and every sampled row has
    to have been compared. The other numbers go beside them with no limit."""
    out = {k: {"value": numbers[k], "limit": float(lim)} for k, lim in limits.items()}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"] for v in out.values())
    out["rows"] = {"value": numbers["rows"], "limit": rows_min}
    for k in numbers:
        if k != "rows" and k not in out:
            out[k] = {"value": numbers[k], "limit": None}
    return bool(ok and numbers["rows"] >= rows_min), out
