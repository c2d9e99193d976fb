"""Share of the linear-attention layers' rows that the window's sweeps
dispatched with the Pallas kernel and not with the XLA op
(``linear_rows_kernel`` / (``linear_rows_kernel`` + ``linear_rows_xla``) of
the program's sweep account): 100, or the cell fell back."""

from benchmark.families.minicpm_sala import readers


def read(run):
    rows = readers.linear_rows(run)
    return None if rows is None else 100.0 * rows[0] / (rows[0] + rows[1])
