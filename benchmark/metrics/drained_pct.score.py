"""Share of the sweep the device stood drained between shards, by the
program's own stamps (the record's ``drained_s``): a shard-end wait for the
device had returned and the next shard's first block was not dispatched yet.
0 where the field is 0; nothing where the program keeps no such field."""

from benchmark import sweep_account


def read(run):
    return sweep_account.share_of_wall(run, "drained_s")
