"""Median host wall of one batch: ``run_prompts`` returns fetched scores, so
the wall ends in the device's completion."""

import statistics


def read(run):
    walls = run["counters"].get("batch_walls")
    return statistics.median(walls) if walls else None
