"""Share of the window's decoder-layer visits whose weights came from a seat
of the residency tier and not from an upload (``visits_pinned`` /
``layer_visits`` of the program's sweep account): a looped model visits each
layer ``total_ut_steps`` times a batch; 100, or the loop is re-streaming."""

from benchmark.families.ouro import readers


def read(run):
    v = readers.visits(run)
    return None if v is None else 100.0 * v[0] / v[1]
