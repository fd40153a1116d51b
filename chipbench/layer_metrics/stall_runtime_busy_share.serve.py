"""Of the stalled turns' excess inside ``engine.wait``, the share during
which the process's OTHER threads were on a CPU (the wait's process CPU
less its thread CPU): high, the runtime was working; near 0, the whole
process slept.  0 where no wait grew.  program_span."""
import stall_readers


def read(cell, window, counters, trace):
    return stall_readers.stall_runtime_busy_share(window)
