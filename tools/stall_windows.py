#!/usr/bin/env python3
"""Stall forensics on the chip: one benchmark cell set up once, N untraced
windows, and after each ``profiler.stalls()`` over that window.

    python3 tools/stall_windows.py --workload <cell> --seed <n> \\
        --windows 8 --seconds 30 --out chiprun_out/stalls_<cell>.jsonl

Uses the benchmark's own cell data, driver and window
(``chipbench/run.py`` ``load_cell`` / ``prepare``, the driver's
``Session.setup()`` and ``measure()``), from the checkout it is started
in, so a window here is a window of the driver's check; what differs is
that the process lives on from one window to the next.  ``--windows 1``
is the check's own protocol.  One JSON line a window: the rate, the
turns' number and median, the six stall metrics by their readers, and
``profiler.stalls()``'s records (every one of ``--keep-ms`` or more, the
count of the rest).

Three witnesses from outside the program say what a stall was not
(``--witness``): every pass of Python's collector with its length
(``gc.callbacks``); a thread of this process that sleeps 2 ms at a time and
notes when it wakes 20 ms late or more (it needs the interpreter's lock, so
it stalls with the process, or with whoever holds the lock); and a child
process that does the same and shares nothing with this one but the
machine (``time.perf_counter`` is the system's monotonic clock in both).
Each kept stall then says how much of it a collection covered and whether
either witness woke late inside it.

``--cost`` first times 100,000 ``span()``, ``span(cpu=True)`` and
``span(os=True)`` in this process, with the runtime's threads alive
(the process's CPU clock walks all of them); ``--hlo`` prints the sha256
of the cell's step program lowered for the device it runs on (a serving
cell: both schedules; each Pallas kernel's Mosaic body printed without
its source locations), for comparing two commits.  Both work on a
checkout from before ``profiler.stalls`` (``--windows 0``).
"""
import argparse
import base64
import gc
import hashlib
import json
import os
import re
import statistics
import subprocess
import sys
import threading
import time
import timeit

ROOT = os.getcwd()
sys.path[:0] = [ROOT, os.path.join(ROOT, "chipbench")]


LATE = 0.020        # a witness that wakes this late says so
NAP = 0.002


def nap_loop(note, stop=lambda: False):
    """Sleep ``NAP`` at a time; ``note(t0, t1)`` each sleep that lasted
    ``LATE`` longer than asked."""
    t0 = time.perf_counter()
    while not stop():
        time.sleep(NAP)
        t1 = time.perf_counter()
        if t1 - t0 >= NAP + LATE:
            note(t0, t1)
        t0 = t1


class Witnesses:
    """The collector's passes, and the sleeps of a thread and of a child
    process that woke late: lists of ``(t0, t1)`` on ``perf_counter``."""

    def __init__(self, path):
        self.gc, self.thread, self.path = [], [], path
        self._gc_t0, self._stop = None, False
        gc.callbacks.append(self._on_gc)
        threading.Thread(target=nap_loop, daemon=True, args=(
            lambda a, b: self.thread.append((a, b)),
            lambda: self._stop)).start()
        self.child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--nap-child", path])

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc.append((self._gc_t0, time.perf_counter(),
                            info["generation"]))

    def child_naps(self):
        try:
            with open(self.path) as f:
                return [tuple(map(float, line.split())) for line in f]
        except OSError:
            return []

    def over(self, t0, t1):
        """Of ``[t0, t1]``: seconds a collection covered (and the oldest
        generation collected), the longest late sleep of the thread and
        of the child that overlaps it."""
        def longest(naps):
            return max((b - a for a, b in naps if a < t1 and b > t0),
                       default=0.0)
        passes = [(min(b, t1) - max(a, t0), g) for a, b, g in self.gc
                  if a < t1 and b > t0]
        return {"gc_s": sum(d for d, _ in passes),
                "gc_generation": max((g for _, g in passes), default=None),
                "thread_late_s": longest(self.thread),
                "child_late_s": longest(self.child_naps())}

    def close(self):
        self._stop = True
        gc.callbacks.remove(self._on_gc)
        self.child.terminate()
        self.child.wait(timeout=10)


def span_cost(profiler, n=100000):
    """us a span, by kind: the best of five loops of ``n``."""
    def loop(**kw):
        def body():
            with profiler.span("cost", **kw):
                pass
        return 1e6 * min(timeit.repeat(body, number=n, repeat=5)) / n
    plain = loop()
    out = {"span_us": plain}
    if "OS_FIELDS" in dir(profiler):
        cpu, full = loop(cpu=True), loop(os=True)
        # an engine step: ``engine.step`` os=True, ``engine.wait`` cpu=True
        out.update(span_cpu_us=cpu, span_os_us=full,
                   step_us=(full - plain) + (cpu - plain),
                   thread_time_us=1e6 * min(timeit.repeat(
                       time.thread_time, number=n, repeat=5)) / n,
                   process_time_us=1e6 * min(timeit.repeat(
                       time.process_time, number=n, repeat=5)) / n)
    return out


# a Mosaic kernel's body inside the StableHLO: base64 MLIR bytecode
MOSAIC_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def sha(lowered):
    """sha256 of the lowered text with each Mosaic body printed WITHOUT
    its locations: a body carries the file:line of every operation, so
    an edit anywhere in a kernel's file would move a raw hash."""
    from jaxlib.mlir import ir

    def body(match):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)
    text = MOSAIC_BODY.sub(body, lowered.as_text())
    return hashlib.sha256(text.encode()).hexdigest()


def step_hlo(session):
    """sha256 of the StableHLO of the cell's step program, lowered for
    the device it runs on from the shapes of one real call."""
    import jax

    def shapes(args):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding)
            if isinstance(a, jax.Array) else a, args)

    if not hasattr(session, "engine"):
        batch = jax.device_put(session.batches[0])
        return {"train": sha(session.step.lower(
            shapes(session.state), shapes(batch), session.rng))}
    import tracing
    from mxnet_tpu.serving import engine as E
    eng, seen = session.engine, {}
    real = eng._step_fn

    def spy(*args):
        seen["args"] = shapes(args)
        return real(*args)

    eng._step_fn = spy
    try:
        while "args" not in seen:
            session._turn(tracing.no_span)
    finally:
        eng._step_fn = real
    args = seen["args"]
    serial = E._make_step(eng.cfg, eng.num_slots, eng.n_rows,
                          eng.pages_per_slot, eng.page_size, eng.kv_int8,
                          kernel=eng.kernel, n_sample=1 + eng.spec_K,
                          overlap=False)
    return {"pipelined" if eng.overlap else "serial": sha(real.lower(*args)),
            "serial_schedule": sha(serial.lower(
                *(args[:-2] if eng.overlap else args)))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--windows", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--keep-ms", type=float, default=50.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cost", action="store_true")
    ap.add_argument("--hlo", action="store_true")
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--gc-off", action="store_true",
                    help="gc.disable() after set-up: what stalls go with it")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import run
    cell = run.load_cell(args.workload, args.seed, args.rehearse)
    driver = run.prepare(cell)
    from mxnet_tpu import profiler
    session = driver.Session(cell)
    session.setup()
    head = {"workload": args.workload, "seed": args.seed,
            "device": cell["device"]}
    if args.cost:
        head["cost"] = span_cost(profiler)
    if args.hlo:
        head["hlo_sha256"] = step_hlo(session)
    print(json.dumps(head), flush=True)
    out = open(args.out, "a") if args.out else None
    if out:
        out.write(json.dumps(head) + "\n")

    if args.gc_off:
        gc.disable()
    witnesses = Witnesses((args.out or "/dev/null") + ".naps") \
        if args.witness else None
    train = not hasattr(session, "engine")
    name = "train.step" if train else "engine.step"
    readers = {m["name"]: run.load_module("layer_metrics", m["name"]).read
               for m in run.metrics_for(cell, "per_layer")
               if "stall" in m["name"]}
    for i in range(args.windows):
        w = session.measure(args.seconds)
        seconds = w["t1"] - w["t0"]
        # (a checkout from before ``profiler.stalls``: the witnesses alone)
        found = [r for r in getattr(profiler, "stalls", lambda *a, **k: [])(
            name, since=w["t0"]) if r["t0"] < w["t1"]]
        kept = [r for r in found if 1e3 * r["excess_s"] >= args.keep_ms]
        starts = sorted(s.t0 for s in profiler.recent_spans()
                        if s.name == name and w["t0"] <= s.t0 < w["t1"])
        line = {
            "workload": args.workload, "seed": args.seed, "window": i,
            "since_start_s": w["t0"] - run.T_START, "seconds": seconds,
            "tok_s": w["tokens"] / seconds, "turns": len(starts),
            "median_turn_ms": 1e3 * statistics.median(
                b - a for a, b in zip(starts, starts[1:])) if starts[1:]
            else float("nan"),
            "metrics": {m: read(cell, w, w.get("counters", {}), None)
                        for m, read in readers.items()},
            "stalled": len(found),
            "stalled_s": sum(r["excess_s"] for r in found),
            "stalls": [dict(r, at_s=r["t0"] - w["t0"]) for r in kept]}
        if witnesses:
            for r in line["stalls"]:
                r["witness"] = witnesses.over(r["t0"], r["t0"] + r["turn_s"])
            line["gc_passes"] = [
                {"at_s": a - w["t0"], "ms": 1e3 * (b - a), "generation": g}
                for a, b, g in witnesses.gc
                if w["t0"] <= a < w["t1"] and b - a >= 0.005]
            line["late_naps"] = {
                who: [{"at_s": a - w["t0"], "ms": 1e3 * (b - a)}
                      for a, b in naps if w["t0"] <= a < w["t1"]]
                for who, naps in (("thread", witnesses.thread),
                                  ("child", witnesses.child_naps()))}
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
        print("window %d: %.1f tok/s, %d turns of %.3f ms, %d stalled "
              "(%.3f s), longest %.1f ms%s" % (
                  i, line["tok_s"], line["turns"], line["median_turn_ms"],
                  len(found), line["stalled_s"],
                  1e3 * max((r["excess_s"] for r in found), default=0.0),
                  "".join("\n    +%.3f s  %7.1f ms  %s / %s, runtime %s%s"
                          % (r["at_s"], 1e3 * r["excess_s"], r["where"],
                             r["cause"], r["runtime"],
                             "; gc %(gc_s).3f s, thread late %(thread_late_s)"
                             ".3f s, child late %(child_late_s).3f s"
                             % r["witness"] if witnesses else "")
                          for r in line["stalls"])), flush=True)
    if witnesses:
        witnesses.close()
    if out:
        out.close()
    if args.windows:        # (it releases what a window left)
        session.release()
    return 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["--nap-child"]:
        with open(sys.argv[2], "w") as naps:
            nap_loop(lambda a, b: (naps.write("%r %r\n" % (a, b)),
                                   naps.flush()))
    sys.exit(main())
