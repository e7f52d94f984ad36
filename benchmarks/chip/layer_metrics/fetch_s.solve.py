"""Seconds per completed solve spent copying the result to the host: the
program's solve.fetch spans, summed over the traced window."""
from spans import seconds_per_solve


def read(run):
    return seconds_per_solve(run, {"solve.fetch"})
