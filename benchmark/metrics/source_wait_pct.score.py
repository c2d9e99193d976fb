"""Share of the sweep the consumer stood blocked on the weight source:
compute starved for weights, at the line where it blocks."""

from benchmark import sweep_account


def read(run):
    return sweep_account.share_of_wall(run, "source_wait_s")
