"""Host seconds per completed solve in the coarsen engine's host phases:
the program's coarsen.canonical span (the graph's host canonicalization
and its upload) plus coarsen.finalize (level picks and residual merged
into original ids), summed over the traced window."""
from spans import seconds_per_solve


def read(run):
    return seconds_per_solve(run, {"coarsen.canonical", "coarsen.finalize"})
