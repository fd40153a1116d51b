"""Share of the page walk's chain slots that held a live group: 100 x
``kv_groups_live`` / ``kv_chain_slots``, from the engine's own ``stats``
counters.  The walk takes the (row, group) items of a block of rows a few
at a time, each folded by a chain of its own out of its own slot of the
ring of copies (``kernels/paged_attention.py walk_geometry``); a block's
last trip runs the chains of the slots it has no item for over what they
hold, masked.  ``kv_groups_live`` is the groups that hold a live page,
``kv_chain_slots`` those rounded up, block by block, to whole trips.
Nothing where the
program books no such counters (the parent commit) or no step walked (the
gather path, the per-page grid).  program_counter."""


def read(cell, window, counters, trace):
    slots = counters.get("kv_chain_slots")
    return 100.0 * counters["kv_groups_live"] / slots if slots else None
