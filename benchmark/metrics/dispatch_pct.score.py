"""Share of the sweep that is the consumer's own host work inside the
shards' compute (the record's ``dispatch_s``: the ``compute`` spans less
every wait for the device): dispatching steps, the store's bookkeeping."""

from benchmark import sweep_account


def read(run):
    return sweep_account.share_of_wall(run, "dispatch_s")
