"""Segment-min reductions of the batch solves, as a share of their
roofline: least time / device time of every reduction in the window.
Least time = (8 E + 4 S) bytes / peak HBM bandwidth for a reduction of
E slots into S segments, E and S read from each event's shapes."""
from trace_reduce import segmin_roofline_pct


def read(run):
    return segmin_roofline_pct(run)
