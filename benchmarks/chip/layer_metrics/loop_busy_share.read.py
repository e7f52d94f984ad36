"""Share of the traced window in which the server's event loop did its
own work: the union of the program's serve.decode, serve.flush and
serve.respond spans over the window, in percent."""
from spans import union_share_pct


def read(run):
    return union_share_pct(run.trace, {"serve.decode", "serve.flush", "serve.respond"})
