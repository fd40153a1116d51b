"""Mean time from the client's submit to the first token, over ALL
requests submitted inside the window: the share of requests that wait a
second or a third engine step for their prefill shows here.  host_clock."""
import numpy as np


def read(cell, window, counters, trace):
    if not len(window["ttft_ms"]):
        return None
    return float(np.mean(window["ttft_ms"]))
