"""Median time to first token, from each request's scheduled arrival.

Over every request due in the window (open loop, so queueing counts).  A
request that failed, or got no first token within ``grace_s`` of the
close, counts as infinitely late; a median that lands on one is an error,
not a number.
"""

import math
import statistics


def read(run):
    due = [r for r in run.requests if r.due]
    if not due:
        return None
    late = [r.first - r.arrival if run.answered(r) else math.inf for r in due]
    value = statistics.median(late)
    if math.isinf(value):
        raise RuntimeError(f"{sum(map(math.isinf, late))} of {len(late)} requests due got no first token")
    return value
