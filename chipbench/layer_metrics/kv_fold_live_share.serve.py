"""Share of the K/V pages the page walk folded that were live: 100 x
``kv_pages_read`` / ``kv_pages_folded``, from the engine's own ``stats``
counters.  The walk copies each row's live pages and folds them in whole
turns of F pages (``kernels/paged_attention.py walk_geometry``), so a row's
last turn may run its two contractions over pages past the row's position,
masked; ``kv_pages_folded`` is each row's live pages rounded up to a whole
turn.  Nothing where the program books no such counter (the parent commit)
or no step walked (the gather path, the per-page grid).  program_counter."""


def read(cell, window, counters, trace):
    folded = counters.get("kv_pages_folded")
    return 100.0 * counters["kv_pages_read"] / folded if folded else None
