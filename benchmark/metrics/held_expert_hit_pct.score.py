"""Share of the router's assignments that landed on an expert this chip
holds: ``held_expert_hits`` / ``routed_assignments`` of the program's sweep
account, summed over the window's sweeps (6.25 on uniform routing over 16 of
256 experts)."""

from benchmark.families.mimo_v2_flash import readers


def read(run):
    counts = readers.expert_counts(run)
    return None if counts is None else 100.0 * counts[0] / counts[1]
