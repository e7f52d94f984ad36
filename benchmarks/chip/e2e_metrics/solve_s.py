"""solve_s: the whole window over the solves that completed, host clock.
A solve ends with its forest's edge ids and labels on the host."""


def read(run):
    done = [o for o in run.of("solve") if o.ok]
    return run.window_s / len(done) if done else None
