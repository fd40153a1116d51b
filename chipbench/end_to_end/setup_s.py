"""Process start to window open: imports, weights, compilation (or its
retrieval from the cache) and warm-up.  host_clock."""


def read(cell, window, counters, trace):
    return window["setup_s"]
