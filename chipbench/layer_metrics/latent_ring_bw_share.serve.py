"""Share of the device step that the latent rows the sliding layers'
rings had to be read for fill at the HBM peak: 100 x (``win_positions``,
the positions the window layers' attention read, summed over those
layers, x one row's required bytes (``kv_lora_rank + qk_rope_head_dim``
values) / ``steps``) / (``hbm_bytes_per_s`` x ``step_device_ms``).  The
latent ring's roofline by bytes as far as the reducer can read it (no
time by scope yet).  Nothing where the configuration keeps no latent
ring, the program books no such counter or no trace was taken.
device_trace."""
import model_math
import model_math_motif3


def read(cell, window, counters, trace):
    sizes = cell["config"]
    positions, steps = counters.get("win_positions"), counters.get("steps")
    if "kv_lora_rank" not in sizes or "sliding_window_period" not in sizes \
            or not positions or not steps or trace is None \
            or not trace.get("step_device_ms"):
        return None
    peak = model_math.peaks(cell["device"]["kind"])["hbm_bytes_per_s"]
    moved = model_math_motif3.ring_read_bytes(sizes, positions)
    return 100.0 * (moved / steps) / (peak * trace["step_device_ms"] * 1e-3)
