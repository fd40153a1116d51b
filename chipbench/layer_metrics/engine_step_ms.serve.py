"""Median wall time of one ``ServingEngine.step()`` over the window: the
harness's clock around the call.  host_clock."""
import numpy as np


def read(cell, window, counters, trace):
    if not len(window["engine_step_ms"]):
        return None
    return float(np.median(window["engine_step_ms"]))
