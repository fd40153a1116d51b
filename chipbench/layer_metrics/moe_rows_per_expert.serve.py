"""Rows a held expert sees in a step that reads it: ``moe_pairs`` /
``moe_experts_hit`` over the window (row-expert pairs dispatched to
experts held here, over held experts with at least one live row, both
summed over layers and steps, counted on the device).  What the expert
layer's arithmetic intensity follows: the deployment's experts would see
ep_chips times as many.  Nothing where the program books no such
counters.  program_counter."""


def read(cell, window, counters, trace):
    hit = counters.get("moe_experts_hit")
    return counters["moe_pairs"] / hit if hit else None
