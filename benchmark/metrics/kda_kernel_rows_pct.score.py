"""Share of the KDA layers' rows that the window's sweeps dispatched with the
Pallas kernel and not with the XLA op (``kda_rows_kernel`` /
(``kda_rows_kernel`` + ``kda_rows_xla``) of the program's sweep account): 100,
or the cell fell back."""

from benchmark.families.glm5_next_text import readers


def read(run):
    rows = readers.kda_rows(run)
    return None if rows is None else 100.0 * rows[0] / (rows[0] + rows[1])
