"""Share of the device step that the recurrent state's traffic fills at
the HBM peak: 100 x (``ssm_state_bytes`` / ``steps``, the window's
counters: every live slot's state read and written once a layer) /
(``hbm_bytes_per_s`` of peaks.json x ``step_device_ms``).  The scan's
roofline as far as the reducer can read it (no time by scope yet).
The counter books what the recurrence requires, not what the program
moves: its pass over the pool reads and rewrites idle slots' states too,
so the share is the traffic's only while every slot is live (as in a
closed loop with a client for every slot).  "Higher" reads a faster
pass over the same bytes; a change that cuts the bytes (a narrower
state) lowers it and has to be read beside ``step_device_ms.serve``.
Nothing where the program books no such counter or no trace was taken.
device_trace."""
import model_math


def read(cell, window, counters, trace):
    moved, steps = counters.get("ssm_state_bytes"), counters.get("steps")
    if not moved or not steps or trace is None \
            or not trace.get("step_device_ms"):
        return None
    peak = model_math.peaks(cell["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (moved / steps) / (peak * trace["step_device_ms"] * 1e-3)
