"""Share of the sweep spent in its head (entry of ``run_prompts`` -> the
consumer's first wait for a shard) and its tail (last shard dispatched ->
scores on the host): the part that is not pipelined because every call builds
and drains its own."""

from benchmark import sweep_account


def read(run):
    return sweep_account.share_of_wall(run, "head_s", "tail_s")
