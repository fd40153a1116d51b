"""Share of the device step that the routed experts' weights fill at the
HBM peak: 100 x (``moe_expert_bytes`` / ``steps``, the window's counters:
every held expert with at least one live row read once a layer) /
(``hbm_bytes_per_s`` of peaks.json x ``step_device_ms``).  The grouped
matmul's roofline as far as the reducer can read it (no time by scope
yet): with a handful of rows an expert the product is bound by its
weights' bytes.  "Higher" reads a faster pass over the same bytes.
Nothing where the program books no such counter or no trace was taken.
device_trace."""
import model_math


def read(cell, window, counters, trace):
    moved, steps = counters.get("moe_expert_bytes"), counters.get("steps")
    if not moved or not steps or trace is None \
            or not trace.get("step_device_ms"):
        return None
    peak = model_math.peaks(cell["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (moved / steps) / (peak * trace["step_device_ms"] * 1e-3)
