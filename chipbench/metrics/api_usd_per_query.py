"""Cloud API dollars per query due in the window: the token-metered
``QueryResult.api_cost``. A latency gain bought by routing everything to
the cloud shows here."""
import numpy as np


def read(r):
    qs = r.result.get("queries_due")
    if not qs:
        return None
    return float(np.mean([q.api_cost for q in qs]))
