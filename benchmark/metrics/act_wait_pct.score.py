"""Share of the sweep the consumer stood blocked on the device inside the
activation store (where a block's device->host copy is resolved one store
later): what sending activations over the link costs; near 0 once the store
keeps the blocks on the chip."""

from benchmark import sweep_account


def read(run):
    return sweep_account.share_of_wall(run, "act_wait_s")
