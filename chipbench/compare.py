"""The arithmetic that decides ``correct``: each number compared, beside its
limit.  Limits live in ``limits/<cell>.json``, one per number, set from
readings on the chip (PERF.md section 2 gives the readings)."""
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def rel_gap(got, want):
    return abs(float(got) - float(want)) / max(abs(float(want)), 1e-30)


def worst_norm_gap(got, want, keep=None):
    """Worst leaf's gap between the program's norm and the reference's --
    the gap of the norms, not the norm of a difference -- against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero).  Returns (gap, leaf index)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    floor = float(np.median(want))
    gaps = np.abs(got - want) / np.maximum(want, floor)
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def moving_leaves(ref_grad_norms):
    """Leaves whose reference gradient is nought to rounding (a key's bias
    under softmax) move under Adam by round-off alone: the change is
    compared only on leaves whose gradient is at least a thousandth of the
    median leaf's."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= 1e-3 * float(np.median(g))


def load_limits(cell_name, rehearse=False):
    """The cell's limits; ``rehearse`` overlays the file's ``rehearse``
    group, the limits of the toy size (read on the CPU: the toy model's
    logits and gradients are of another scale)."""
    with open(os.path.join(HERE, "limits", cell_name + ".json")) as f:
        data = json.load(f)
    if rehearse:
        data = dict(data, **data.get("rehearse", {}))
    return {k: v for k, v in data.items()
            if not k.startswith("_") and k != "rehearse"}


def judge(readings, limits):
    """readings: {name: value}.  Every limit needs its reading; a reading
    that is missing, not finite or over its limit makes the run not
    correct.  A limit of null marks a number that is printed beside the
    others and not compared (a part of one that is).  Returns (correct,
    {name: {"value", "limit"}})."""
    out, ok = {}, True
    for name, limit in limits.items():
        value = readings.get(name)
        good = limit is None or (value is not None and np.isfinite(value)
                                 and value <= limit)
        ok = ok and bool(good)
        out[name] = {"value": None if value is None else float(value),
                     "limit": limit}
    return ok, out
