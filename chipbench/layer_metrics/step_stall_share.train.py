"""Share of the window lost to stalled training turns: with T the time
from one ``train.step`` dispatch to the next (the window's last to its
end, which the wait for the last state closes) and m the median, 100 x
the sum over T > 1.5 m of (T - m), over the window's length.  Nothing
where the program opens no such span.  program_span."""
import stall_readers


def read(cell, window, counters, trace):
    return stall_readers.turn_stall_share(window, stall_readers.TRAIN_STEP)
