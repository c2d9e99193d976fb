"""The window's sweeps as the program itself accounted for them: the last
``counters["batches"]`` records of the executor's ``process_sweep_log()``
(one batch is one sweep at the default ``num_batch``), held against the
driver's own batch walls. The five ``*.score`` readers that divide a record's
fields share this; each reports the median over the window's sweeps.

A program without the log (every commit before PR 25) gives nothing, and so
does a window the log does not cover or does not match: a reader then returns
None and its metric is left out of the line.
"""

from __future__ import annotations

import statistics

WALL_SLACK = 0.03  # a record's wall_s lies within this share UNDER its batch wall
CLOCK_EPS_S = 1e-4  # two monotonic clocks read a few lines apart


def match(records: list[dict], walls: list[float]) -> list[dict] | None:
    """``records`` if there is one per wall, oldest first, and each one's
    ``wall_s`` (``run_prompts`` about to build its executor -> scores returned) lies within
    ``WALL_SLACK`` under the same call's wall as timed from outside."""
    n = len(walls)
    if not n or len(records) < n:
        return None
    records = records[-n:]
    for rec, wall in zip(records, walls):
        if not (1.0 - WALL_SLACK) * wall <= rec["wall_s"] <= wall + CLOCK_EPS_S:
            return None
    return records


def window(run) -> list[dict] | None:
    """The records of the run's window, or None."""
    from flexible_llm_sharding_tpu.runtime import executor

    log = getattr(executor, "process_sweep_log", None)
    c = run["counters"]
    walls = c.get("batch_walls") or []
    if log is None or len(walls) != c.get("batches"):
        return None
    return match(log(), walls)


def median_of(run, value) -> float | None:
    """Median over the window's sweeps of ``value(record)``; None when there
    is no window or a sweep has nothing to divide by."""
    records = window(run)
    if records is None:
        return None
    try:
        return statistics.median(value(r) for r in records)
    except (KeyError, ZeroDivisionError):
        return None


def share_of_wall(run, *fields: str) -> float | None:
    """100 x the sum of ``fields`` over ``wall_s``, median over the sweeps."""
    return median_of(run, lambda r: 100.0 * sum(r[f] for f in fields) / r["wall_s"])
