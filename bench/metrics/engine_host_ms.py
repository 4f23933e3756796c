"""Engine loop: mean host bookkeeping per consumed decode step in the
window (the engine's ``step_host_s``, as ``stats()["host_mean_s"]``)."""

import numpy as np


def read(run):
    h = run.window.step_host_s
    return float(np.mean(h)) * 1e3 if h else None
