"""Seconds per completed solve in the coarsening levels: the program's
coarsen.level spans, each from the level's dispatch to its scalars and
hooked edge ids on the host, summed over the traced window."""
from spans import seconds_per_solve


def read(run):
    return seconds_per_solve(run, {"coarsen.level"})
