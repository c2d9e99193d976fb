"""Share of the sweep in which shards whose own weights had arrived were
launched behind ANOTHER shard's upload, enqueued an instant before (the
record's ``behind_upload_s``): what the order of dispatch costs. 0 where
nothing streams; nothing where the program keeps no such field."""

from benchmark import sweep_account


def read(run):
    return sweep_account.share_of_wall(run, "behind_upload_s")
