"""Share of the device step that the latent rows the page walk had to
read fill at the HBM peak, counting only the layers that keep pages:
100 x (``kv_pages_read`` x page size x one row's required bytes
(``kv_lora_rank + qk_rope_head_dim`` values) x paged layers / ``steps``)
/ (``hbm_bytes_per_s`` x ``step_device_ms``).  For a configuration whose
sliding layers keep a latent ring and whose full layers keep latent
pages (``model_math_motif3``); ``latent_read_bw_share.serve`` is the
same share where every layer keeps pages.  The walk's roofline by bytes
as far as the reducer can read it (no time by scope yet).  Nothing where
the configuration mixes no window layers with latent pages, the program
books no such counter or no trace was taken.  device_trace."""
import model_math
import model_math_motif3


def read(cell, window, counters, trace):
    sizes = cell["config"]
    pages, steps = counters.get("kv_pages_read"), counters.get("steps")
    if "kv_lora_rank" not in sizes or "sliding_window_period" not in sizes \
            or not pages or not steps or trace is None \
            or not trace.get("step_device_ms"):
        return None
    peak = model_math.peaks(cell["device"]["kind"])["hbm_bytes_per_s"]
    moved = model_math_motif3.latent_walk_bytes(sizes, pages)
    return 100.0 * (moved / steps) / (peak * trace["step_device_ms"] * 1e-3)
