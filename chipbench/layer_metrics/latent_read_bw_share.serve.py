"""Share of the device step that the latent rows the page walk had to
read fill at the HBM peak: 100 x (``kv_pages_read`` x page size x one
row's required bytes (``kv_lora_rank + qk_rope_head_dim`` values) x
layers / ``steps``) / (``hbm_bytes_per_s`` x ``step_device_ms``).  The
walk's roofline by bytes as far as the reducer can read it (no time by
scope yet); its arithmetic is 121 operations a byte, half the chip's
ridge, so neither bound is far.  Nothing where the configuration keeps
no latent rows, the program books no such counter or no trace was taken.
device_trace."""
import model_math
import model_math_deepseek_v3


def read(cell, window, counters, trace):
    sizes = cell["config"]
    pages, steps = counters.get("kv_pages_read"), counters.get("steps")
    if "kv_lora_rank" not in sizes or not pages or not steps \
            or "moe_pairs" not in counters or trace is None \
            or not trace.get("step_device_ms"):
        return None
    peak = model_math.peaks(cell["device"]["kind"])["hbm_bytes_per_s"]
    moved = model_math_deepseek_v3.latent_read_bytes(
        sizes, pages, sizes["engine"]["page_size"])
    return 100.0 * (moved / steps) / (peak * trace["step_device_ms"] * 1e-3)
