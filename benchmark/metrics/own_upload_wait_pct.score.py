"""Share of the sweep in which shards were launched before their OWN weight
upload had arrived (the record's ``own_upload_wait_s``): the link's honest
turn, which only fewer bytes or a faster link shorten. 0 where nothing
streams; nothing where the program keeps no such field."""

from benchmark import sweep_account


def read(run):
    return sweep_account.share_of_wall(run, "own_upload_wait_s")
