"""Hook-and-shortcut rounds per solve (SolveReport.iterations, the
levels' rounds and the residual's), a count, mean over the window."""


def read(run):
    its = [o.info["iterations"] for o in run.of("solve") if o.ok]
    return sum(its) / len(its) if its else None
