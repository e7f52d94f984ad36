"""Query points per fused device call in the window: the server's
serve.queries counter over its serve.batch_occupancy count (read with the
metrics op before and after the window)."""


def read(run):
    b = run.facts.get("fused_batches")
    return run.facts["served_queries"] / b if b else None
