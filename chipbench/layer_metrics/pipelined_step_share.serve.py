"""Share of the window's committed engine steps that were dispatched
pipelined -- launched against the step before's device-resident tokens,
before the host had read that step back: 100 x ``overlap_steps`` / ``steps``,
from the engine's own ``stats`` counters.  0 under the serial schedule, 100
where every step of the window rode the pipeline (a speculation fence runs
its step serially and counts against it).  Nothing where the program books
no such counter or committed no step in the window.  program_counter."""


def read(cell, window, counters, trace):
    steps = counters.get("steps")
    if not steps or "overlap_steps" not in counters:
        return None
    return 100.0 * counters["overlap_steps"] / steps
