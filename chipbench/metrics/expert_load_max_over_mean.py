"""Expert load imbalance in decode: the (token, expert) pairs of the most
loaded held expert over the mean of the held experts, from the engine's
``expert_picks_<i>`` counters (the rows that serve a request, in every
decode step of the run: warm-up, window and drain; the harness keeps no
counters at the window's edges). 1 is an even load. Nothing where the
program keeps no such counter."""


def read(r):
    c = r.result.get("counters", {}).get("engine", {})
    picks = [float(v) for k, v in c.items() if k.startswith("expert_picks_")]
    if not picks or sum(picks) <= 0:
        return None
    return max(picks) / (sum(picks) / len(picks))
