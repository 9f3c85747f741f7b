"""95th percentile of the window's step times, in milliseconds: each step
is one ``fit(1)``, timed on the host from the call to the loss as a
Python float (the device's result)."""

import numpy as np


def read(run):
    return float(np.percentile(np.asarray(run.walls) * 1e3, 95))
