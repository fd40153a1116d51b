"""Per-layer metrics that say what a stall was, read from the program's
spans and from what the OS says of the thread at their ends.

Since PR 38 the program's ``engine.step`` and ``train.step`` spans carry
two tuples of cumulative readings in their ``args``, ``os0`` when the span
opened and ``os1`` when it closed -- ``FIELDS`` below -- and an engine
step's ``engine.wait`` the first two of them (``cpu0``, ``cpu1``).  The readers
here work on TURNS: a turn runs from the start of one step to the start of
the next on its thread, so it holds the step and whatever followed it (the
harness's side, a pause between two steps); the turns of the measured
window are those of the steps that START in ``[window["t0"],
window["t1"])``, and the last one ends at the window's end.  With T the
turns' lengths and m their median, a turn stalled where T > 1.5 m, and its
excess is e = T - m.

A program whose spans carry no readings (a commit from before it had
them), or that opens no such span, gives every reader nothing to read: it
returns None and the line leaves the metric out.

Reading a stall (docs/observability.md has the whole of it): inside
``engine.wait`` with the NEXT step's wait normal, the device was late;
with the next wait collapsed, the device had run ahead and the read-back
was late; outside the wait with the thread on a CPU, the host's own code
ran that long; off it, the kernel took the thread away or it blocked.
"""
import statistics

import span_readers

FIELDS = ("thread_cpu_s", "process_cpu_s", "vol_switches", "invol_switches",
          "minor_faults", "major_faults", "runq_wait_s")
THREAD, PROCESS = FIELDS.index("thread_cpu_s"), FIELDS.index("process_cpu_s")
WAIT = "engine.wait"
TRAIN_STEP = "train.step"
COLLAPSED = 0.5         # a wait under this x the median wait has collapsed


def _args(span):
    return span[6] if len(span) > 6 and isinstance(span[6], dict) else {}


def _clock(span, key, i):
    """Field ``i`` of the reading ``key`` of a span, or None."""
    reading = _args(span).get(key)
    return None if reading is None else reading[i]


def turns_in(spans, name, t0, t1):
    """One dict for each span called ``name`` that starts in ``[t0, t1)``,
    in order of start: ``turn_s`` (to the next one's start on its thread,
    be that after the window; the last of the window's to ``t1``),
    ``cpu_s`` (the thread's CPU over the turn, by the ``os0`` of the two
    spans; of the last span of the ring, to its own ``os1``), ``wait_s``,
    ``wait_cpu_s`` and ``wait_others_s`` (its ``engine.wait`` children:
    wall, the thread's CPU, the process's CPU less the thread's) and
    ``next_wait_s`` (the next span's, None where there is none).  None
    where no such span carries readings."""
    by_id = {s[0]: s for s in spans}
    named = sorted((s for s in spans if s[2] == name), key=lambda s: s[3])
    if not any("os0" in _args(s) for s in named):
        return None
    waits = {}
    for s in spans:
        if s[2] != WAIT:
            continue
        up = by_id.get(s[1])
        while up is not None and up[2] != name:
            up = by_id.get(up[1])
        if up is None:
            continue
        w = waits.setdefault(up[0], [0.0, 0.0, 0.0])
        w[0] += s[4] - s[3]
        cpu = [_clock(s, key, i) for key in ("cpu0", "cpu1")
               for i in (THREAD, PROCESS)]
        if None not in cpu:
            w[1] += cpu[2] - cpu[0]
            w[2] += max(0.0, (cpu[3] - cpu[1]) - (cpu[2] - cpu[0]))
    after = {}      # id -> the next span of that name on the same thread
    last = {}
    for s in named:
        if s[5] in last:
            after[last[s[5]][0]] = s
        last[s[5]] = s
    inside = [s for s in named if t0 <= s[3] < t1]
    turns = []
    for s in inside:
        nxt = after.get(s[0])
        a = _clock(s, "os0", THREAD)
        b = _clock(nxt, "os0", THREAD) if nxt is not None \
            else _clock(s, "os1", THREAD)
        wait = waits.get(s[0], [0.0, 0.0, 0.0])
        turns.append({
            "turn_s": (nxt[3] if nxt is not None else s[4]) - s[3],
            "cpu_s": None if a is None or b is None else b - a,
            "wait_s": wait[0], "wait_cpu_s": wait[1],
            "wait_others_s": wait[2],
            "next_wait_s": None if nxt is None
            else waits.get(nxt[0], [0.0])[0]})
    if turns:
        turns[-1]["turn_s"] = t1 - inside[-1][3]
    return turns


def window_turns(window, name=span_readers.STEP):
    """The turns of the measured window, or None where the program keeps
    no spans, none of that name carries readings, or none began in the
    window."""
    spans = span_readers.recent_spans()
    if spans is None:
        return None
    return turns_in(spans, name, window["t0"], window["t1"]) or None


def stalled(turns):
    """``(turn, excess)`` for the stalled turns: longer than
    ``span_readers.STALL_FACTOR`` medians, by how much over the median."""
    median = statistics.median(t["turn_s"] for t in turns)
    return [(t, t["turn_s"] - median) for t in turns
            if t["turn_s"] > span_readers.STALL_FACTOR * median]


def _clamp(x, lo, hi):
    return max(lo, min(x, hi))


def turn_stall_max_ms(window, name=span_readers.STEP):
    """The longest stalled turn's excess, 0 where none stalled: tells one
    hole of 1.5 s from ten of 0.15 s, and sees a pause between two steps."""
    turns = window_turns(window, name)
    if turns is None:
        return None
    return 1e3 * max((e for _, e in stalled(turns)), default=0.0)


def turn_stall_share(window, name):
    """Share of the window lost to stalled turns: 100 x the sum of their
    excess over the window's length."""
    turns = window_turns(window, name)
    if turns is None:
        return None
    return 100.0 * sum(e for _, e in stalled(turns)) \
        / (window["t1"] - window["t0"])


def stall_offcpu_share(window):
    """Of the stalled turns' HOST time over the median turn's (host: the
    turn less its ``engine.wait``), the share the thread spent OFF a CPU
    over the median turn's: high, the kernel took the thread away or it
    blocked; low, the host's own code ran.  0 where no host time grew."""
    turns = window_turns(window)
    if turns is None or any(t["cpu_s"] is None for t in turns):
        return None
    def host(t):
        return t["turn_s"] - t["wait_s"]

    def off(t):
        return host(t) - (t["cpu_s"] - t["wait_cpu_s"])

    host_m = statistics.median(host(t) for t in turns)
    off_m = statistics.median(off(t) for t in turns)
    grown = lost = 0.0
    for t, _ in stalled(turns):
        x = max(0.0, host(t) - host_m)
        grown += x
        lost += _clamp(off(t) - off_m, 0.0, x)
    return 100.0 * lost / grown if grown else 0.0


def _wait_excess(turns):
    """``(turn, w)`` for the stalled turns: w, how much of the turn's
    excess its ``engine.wait`` outlasted the median wait by (no less than
    nothing, no more than the turn's excess); and the median wait."""
    wait_m = statistics.median(t["wait_s"] for t in turns)
    return [(t, _clamp(t["wait_s"] - wait_m, 0.0, e))
            for t, e in stalled(turns)], wait_m


def stall_host_late_share(window, counters):
    """Of the stalled turns' excess inside ``engine.wait``, the share in
    turns whose NEXT step's wait collapsed (under half the median wait):
    the device had run ahead and finished the step in flight while the
    host still waited for the one before, so the READ-BACK was late (the
    runtime, the transfer, or the whole process stopped); low, the device
    itself was late.  0 where no wait grew; nothing where the window's
    ``overlap_steps`` did not move: at pipeline depth 0 no step is in
    flight to run ahead."""
    turns = window_turns(window)
    if turns is None or not counters.get("overlap_steps"):
        return None
    found, wait_m = _wait_excess(turns)
    total = sum(w for _, w in found)
    late = sum(w for t, w in found if t["next_wait_s"] is not None
               and t["next_wait_s"] < COLLAPSED * wait_m)
    return 100.0 * late / total if total else 0.0


def stall_runtime_busy_share(window):
    """Of the stalled turns' excess inside ``engine.wait``, the share
    during which the process's OTHER threads were on a CPU (the wait's
    process CPU less its thread CPU): high, the runtime was working (a
    compile, a transfer, a callback); near 0, the whole process slept.
    0 where no wait grew."""
    turns = window_turns(window)
    if turns is None:
        return None
    found, _ = _wait_excess(turns)
    total = sum(w for _, w in found)
    busy = sum(min(w, t["wait_others_s"]) for t, w in found)
    return 100.0 * busy / total if total else 0.0
