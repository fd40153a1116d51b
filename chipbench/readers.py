"""Arithmetic that several per-layer metrics share.  Each metric still has
a reader file of its own under ``layer_metrics/``, which is what the
harness finds by the metric's name; the files of metrics that are one
quantity read in two kinds of cell import it from here."""
import model_math


def step_device_ms(cell, window, counters, trace):
    """Median device time of one run of the step program in the traced
    slice (the XLA-modules line)."""
    return None if trace is None else trace["step_device_ms"]


def device_idle_share(cell, window, counters, trace):
    """Share of the traced slice in which no operation ran on the device:
    1 - union of the device-op intervals over the slice.  A reader that
    finds no trace returns nothing."""
    if trace is None or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def step_mfu(cell, window, counters, trace):
    """The whole step's share of the chip's peak: the operations the model
    needs for the tokens of the window (model_math, from shapes; no
    recomputation) over window seconds x chips x the bf16 peak."""
    peak = model_math.peaks(cell["device"]["kind"])["bf16_flops_per_s"]
    seconds = window["t1"] - window["t0"]
    return 100.0 * window["flops"] / (seconds * cell["chips"] * peak)
