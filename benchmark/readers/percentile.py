import numpy as np


def read(run, series, q, scale=1.0):
    """The q-th percentile of a series of the window."""
    v = run["numbers"].get(series)
    return float(np.percentile(np.asarray(v, np.float64), q)) * scale \
        if v else None
