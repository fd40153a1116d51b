"""The heaviest expert's rows over the mean expert's, over the window:
``num_experts`` x ``moe_pairs_max`` / ``moe_pairs`` (per expert layer and
step the largest entry of the layer's per-expert pair counts, and all
its pairs; both summed over layers and steps, counted on the device over
live rows).  1.0 is an even layer; with every expert hit every step this,
not ``moe_experts_hit``, says whether a seed's routing is uneven, and the
heaviest expert's rows decide how many 128-row tiles of the grouped
product it spans.  Nothing where the program books no such counter.
program_counter."""


def read(cell, window, counters, trace):
    heaviest, pairs = counters.get("moe_pairs_max"), counters.get("moe_pairs")
    experts = cell["config"].get("num_experts")
    if not heaviest or not pairs or not experts:
        return None
    return experts * heaviest / pairs
