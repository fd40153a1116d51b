"""95th percentile over ALL gaps between consecutive tokens of a request
that closed inside the window, of all requests.  host_clock."""
import numpy as np


def read(cell, window, counters, trace):
    if not len(window["itl_ms"]):
        return None
    return float(np.percentile(window["itl_ms"], 95))
