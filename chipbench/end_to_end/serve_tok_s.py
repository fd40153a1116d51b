"""Every generated token that arrived inside the window, of every request
(those in flight at its edges too), over the window.  host_clock."""


def read(cell, window, counters, trace):
    return window["tokens"] / (window["t1"] - window["t0"])
