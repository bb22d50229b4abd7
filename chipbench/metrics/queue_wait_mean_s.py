"""Scheduler queue wait: mean admission wait of the queries due in the
window (``QueryResult.queue_wait``, arrival to admission), seconds."""
import numpy as np


def read(r):
    qs = r.result.get("queries_due")
    if not qs:
        return None
    return float(np.mean([q.queue_wait for q in qs]))
