"""p95 of the fused query call's host time: the program's stream.query
spans on the server's query thread (snapshot pin, padding, the fused
gather, the answer on the host), over the traced window, in ms."""
from spans import percentile_ms


def read(run):
    return percentile_ms(run.trace, {"stream.query"}, 95)
