"""query_p95_ms: 95th percentile of every query's latency, from its due
time in the open-loop schedule to its response, over raw samples. A
refused, late or unanswered query counts at least the server's deadline."""
import math

import numpy as np


def read(run):
    qs = run.of("connected", "component_id", "component_size")
    if not qs:
        return None
    floor = run.config["serve"]["deadline_ms"] / 1e3

    def latency(o):
        if math.isnan(o.t_done):
            return floor
        lat = o.t_done - o.t_sched
        return lat if o.ok else max(lat, floor)

    return float(np.percentile([latency(o) for o in qs], 95)) * 1e3
