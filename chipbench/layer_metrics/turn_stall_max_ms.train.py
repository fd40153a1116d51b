"""The longest stalled training turn's excess over the median turn, in
ms (turns of ``train.step``, as ``step_stall_share.train`` has them); 0
where none stalled.  program_span."""
import stall_readers


def read(cell, window, counters, trace):
    return stall_readers.turn_stall_max_ms(window, stall_readers.TRAIN_STEP)
