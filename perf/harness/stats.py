"""Order statistics over the readings of one run."""
import numpy as np


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; None for no values."""
    xs = np.asarray(list(values), dtype=np.float64)
    if xs.size == 0:
        return None
    return float(np.percentile(xs, q))


def mean(values):
    xs = list(values)
    return float(np.mean(xs)) if xs else None
