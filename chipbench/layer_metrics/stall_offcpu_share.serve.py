"""Of the stalled turns' host time beyond the median turn's (a turn less
its ``engine.wait``), the share the engine's thread spent OFF a CPU beyond
the median turn's, by the thread's CPU clock at the spans' ends: high, the
kernel took the thread away or it blocked; low, the host's own code ran
that long.  0 where no stalled turn's host time grew.  program_span."""
import stall_readers


def read(cell, window, counters, trace):
    return stall_readers.stall_offcpu_share(window)
