"""All tokens of all steps completed in the window over the window, which
is closed by a wait for the last step's state.  host_clock."""


def read(cell, window, counters, trace):
    return window["tokens"] / (window["t1"] - window["t0"])
