"""Copies of an expert's weights a hit, in the grouped products:
``moe_weight_fetches`` / (3 x ``moe_experts_hit``) over the window.  The
program counts, on the device from each expert layer's sorted group
sizes, the copies of one expert's matrix that the grid of each of the
layer's three grouped products asks for: one for every 128-row tile an
expert's rows touch where the product's weight tiles split K (the block
of the visit before is no longer the one in VMEM), one for every expert
hit where a weight tile spans K.  1.0 is every hit expert copied once a
product; the excess is expert bytes moved twice.  Nothing where the
program books no such counter.  program_counter."""


def read(cell, window, counters, trace):
    fetches, hit = (counters.get("moe_weight_fetches"),
                    counters.get("moe_experts_hit"))
    return fetches / (3 * hit) if fetches and hit else None
