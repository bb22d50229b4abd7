"""Share of the traced window in which no XLA op ran on the device:
1 - (union of device op intervals) / (window), averaged over chips."""


def read(r):
    if r.reduced is None or r.reduced.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.reduced.busy_s / r.reduced.window_s)
