"""Pointer jumps per solve: SolveReport.shortcut_jumps, the p <- p[p]
passes of the flat driver's shortcut over the whole parent vector, a
count, mean over the window's solves that succeeded. None where the
program reports no such count."""


def read(run):
    js = [o.info["shortcut_jumps"] for o in run.of("solve")
          if o.ok and "shortcut_jumps" in o.info]
    return sum(js) / len(js) if js else None
