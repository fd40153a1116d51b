"""95th percentile of the time from the client's submit to the first
token, over ALL requests submitted inside the window.  In a closed loop
driven in step with the engine this is a whole number of engine steps,
and the percentile flips between two of them from run to run: it is read
here, and bounded nowhere (PERF.md section 2).  host_clock."""
import numpy as np


def read(cell, window, counters, trace):
    if not len(window["ttft_ms"]):
        return None
    return float(np.percentile(window["ttft_ms"], 95))
