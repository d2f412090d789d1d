"""90th percentile of the time from a request's due time to the end of the
first step after which it holds a slot (read from the engine's slots, which
the benchmark loop only looks at)."""

import numpy as np


def read(run):
    waits = run.spans.get("queue_wait")
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits), 90)) * 1e3
