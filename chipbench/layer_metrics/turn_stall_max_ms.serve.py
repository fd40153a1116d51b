"""The longest stalled turn's excess over the median turn, in ms; 0 where
none stalled.  A turn runs from the start of one ``engine.step`` to the
start of the next (the window's last to its end), so it holds the step and
the pause that may follow it; stalled is longer than 1.5 medians.  One
hole of 1.5 s and ten of 0.15 s read the same in ``step_stall_share.serve``
and not here.  Nothing where the steps carry no OS readings (a program
from before PR 38).  program_span."""
import stall_readers


def read(cell, window, counters, trace):
    return stall_readers.turn_stall_max_ms(window)
