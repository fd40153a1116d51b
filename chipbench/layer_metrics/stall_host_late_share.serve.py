"""Of the stalled turns' excess inside ``engine.wait``, the share in turns
whose NEXT step's wait collapsed (under half the median wait): the device
had run ahead, so the read-back was late (the runtime, the transfer, the
whole process stopped); low, the device itself was late.  0 where no wait
grew; nothing at pipeline depth 0 (``overlap_steps`` did not move).
program_span."""
import stall_readers


def read(cell, window, counters, trace):
    return stall_readers.stall_host_late_share(window, counters)
