"""Mean host time of one ``ServingEngine.step`` ending in the window,
every engine and replica included; the step ends in its host sync."""
import numpy as np


def read(r):
    d = [s.t1 - s.t0 for _, s in r.recorder.steps_in(*r.window)]
    return 1000.0 * float(np.mean(d)) if d else None
