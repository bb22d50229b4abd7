"""Dispatch wait: mean, over every subtask of the queries due in the
window, of ``SubtaskResult.dispatch_wait`` (fleet clock from routing to
the start of the attempt that produced the result: the wait for a free
executor slot), seconds. Nothing where the program keeps no such
counter."""
import numpy as np


def read(r):
    waits = [getattr(s, "dispatch_wait", None)
             for q in r.result.get("queries_due") or ()
             for s in q.results.values()]
    if not waits or None in waits:
        return None
    return float(np.mean(waits))
