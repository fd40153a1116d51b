#!/usr/bin/env python3
"""chipbench -- one run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell, sets the program up (weights from the seed, every shape the
window uses compiled and warm), measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON line
last on standard output.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` follows the window with a short slice under the
profiler and reports the per-layer metrics.

Everything that belongs to one cell is data, found by the names in
``BENCHMARK.json``: the configuration ``configs/<config>.json`` (which
names its ``drivers/<driver>.py`` and its ``reference/<reference>.py``),
the traffic ``traffic/<traffic>.json`` (which names its
``generators/<generator>.py``), the limits ``limits/<cell>.json``, and one
reader per metric, ``end_to_end/<metric>.py`` or
``layer_metrics/<metric>.py``.  Nothing here names a cell, a
configuration or a metric.

Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result.  ``--rehearse`` runs the same code at the
toy sizes the data files give, on whatever backend JAX finds, to prove
paths and control flow: it writes no device metric.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(kind, name):
    """``chipbench/<kind>/<name>.py`` as a module (a metric's name may hold
    dots, so this goes by path and not by import name)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_%s_%s" % (kind, name.replace(".", "_").replace("-", "_")),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _overlay(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _overlay(base[k], v) \
            if isinstance(v, dict) and isinstance(base.get(k), dict) else v
    return out


def load_cell(name, seed, rehearse):
    """The cell's BENCHMARK.json entry with its data files read in."""
    bench = read_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit("chipbench: no workload %r in BENCHMARK.json (has: %s)"
                         % (name, ", ".join(w["name"]
                                            for w in bench["workloads"])))
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = read_json(ROOT, conf["file"])
    traffic = read_json(HERE, "traffic", entry["traffic"] + ".json")
    if rehearse:
        config = _overlay(config, config.get("rehearse", {}))
        traffic = _overlay(traffic, traffic.get("rehearse", {}))
    return {"name": name, "chips": entry["chips"], "seed": int(seed),
            "config": config, "traffic": traffic, "rehearse": rehearse,
            "bench": bench}


def metrics_for(cell, kind):
    """The metrics of one kind that this cell reports, by BENCHMARK.json:
    a metric with a ``workloads`` key where that lists the cell; an
    end-to-end metric without one everywhere; a per-layer metric without
    one wherever the end-to-end metric it moves is reported."""
    bench = cell["bench"]
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def find_devices(cell):
    """JAX's devices, or an exit: no accelerator, or fewer chips than the
    cell asks for, is no place to take a device metric."""
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if cell["rehearse"]:
        return devices, info
    if info["platform"] != "tpu":
        raise SystemExit("chipbench: needs a TPU; JAX found platform %r and "
                         "will not fall back to it" % info["platform"])
    if info["count"] < cell["chips"]:
        raise SystemExit("chipbench: cell %s asks for %d chips, JAX found %d"
                         % (cell["name"], cell["chips"], info["count"]))
    return devices, info


def prepare(cell):
    """Find the devices and load the cell's code by the names in its data
    files; returns the driver's module.  The driver imports the program,
    which places the compile cache (<checkout>/.jax_cache, or where
    JAX_COMPILATION_CACHE_DIR says), before anything compiles."""
    for p in (ROOT, HERE):    # the program under test lives beside us
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    devices, cell["device"] = find_devices(cell)
    cell["devices"] = devices[:cell["chips"]]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell["generator"] = load_module("generators",
                                    cell["traffic"]["generator"])
    cell["reference"] = load_module("reference", cell["config"]["reference"])
    return load_module("drivers", cell["config"]["driver"])


def memory_peak(devices):
    """Peak bytes on the fullest chip: the allocator's peak of live
    buffers plus the peak it reserved for the running programs' scratch
    (on the TPU the two are counted apart, and a training step's saved
    activations are all in the second)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None,
                    help="directory to copy the traced slice's .xplane.pb to")
    ap.add_argument("--trace-seconds", type=float, default=None,
                    help="length of the traced slice (default: the traffic file's)")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload, args.seed, args.rehearse)
    driver = prepare(cell)
    device = cell["device"]
    tag = "[%s/%s x%d]" % (device["platform"], device["kind"],
                           device["count"])

    def say(msg):
        print("%s %s" % (tag, msg), file=sys.stderr, flush=True)

    cell["keep_trace"] = args.keep_trace
    cell["trace_seconds"] = args.trace_seconds

    import compare
    limits = compare.load_limits(cell["name"], cell["rehearse"])

    say("cell %s seed %d: set-up" % (cell["name"], cell["seed"]))
    session = driver.Session(cell)
    session.setup()
    setup_s = time.perf_counter() - T_START
    say("set-up %.1f s; measuring for %.1f s" % (setup_s, args.seconds))
    window = session.measure(args.seconds, trace=bool(args.trace))
    window["setup_s"] = setup_s
    peak = memory_peak(cell["devices"])
    session.release()
    t_check = time.perf_counter()
    readings = session.check()
    correct, compared = compare.judge(readings, limits)
    say("check %.1f s" % (time.perf_counter() - t_check))

    trace = window.get("trace")
    kind, folder = ("per_layer", "layer_metrics") if args.trace \
        else ("end_to_end", "end_to_end")
    metrics = {}
    if not cell["rehearse"]:
        for m in metrics_for(cell, kind):
            reader = load_module(folder, m["name"])
            value = reader.read(cell, window, window.get("counters", {}),
                                trace)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    device = dict(device, count=cell["chips"], memory_peak_bytes=peak)
    line = {"correct": bool(correct),
            "attempted": int(window["attempted"]),
            "failed": int(window["failed"]), "metrics": metrics,
            "device": device}
    if args.trace and trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    if cell["rehearse"]:
        line["rehearse"] = True
    line["workload"] = cell["name"]
    line["seed"] = cell["seed"]
    line["window_s"] = window["t1"] - window["t0"]
    line["compared"] = compared
    for name, c in compared.items():
        say("compared %s = %r (limit %r)" % (name, c["value"], c["limit"]))
    say("correct = %s" % correct)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
