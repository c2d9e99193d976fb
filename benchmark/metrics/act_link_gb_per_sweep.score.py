"""GB of activations that crossed the link per sweep, both ways (the sweep
record's ``act_bytes``): 0 when the activation store kept every block on the
chip, so that only weights cross."""

from benchmark import sweep_account


def read(run):
    return sweep_account.median_of(run, lambda r: r["act_bytes"] / 1e9)
