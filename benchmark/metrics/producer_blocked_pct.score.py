"""Share of the sweep the producer held a built shard while its queue was
full: the prefetch depth holding the producer, hence the link, back."""

from benchmark import sweep_account


def read(run):
    return sweep_account.share_of_wall(run, "producer_blocked_s")
