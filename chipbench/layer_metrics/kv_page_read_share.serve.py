"""Share of the attention window's K/V pages that the step program's
attention read over the window: 100 x ``kv_pages_read`` / ``kv_pages_window``,
from the engine's own ``stats`` counters.  The window is every row's whole
block table (rows x pages a slot, each dispatched step); the gather path
reads all of it (100), the page walk reads each row up to its own position
and a dead row's scratch page.  Nothing where the program books no such
counters.  program_counter."""


def read(cell, window, counters, trace):
    pages = counters.get("kv_pages_window")
    return 100.0 * counters["kv_pages_read"] / pages if pages else None
