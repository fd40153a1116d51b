"""From a profiler trace to numbers.

Two parts, kept apart so that the arithmetic is tested without a chip:

* ``read_xplane(path)`` -- the adapter: an ``.xplane.pb`` file, read with
  nothing but JAX, flattened to plain events
  ``(plane, line, name, start_ns, dur_ns)``;
* ``reduce(events)`` -- the reducer: device busy time, the traced window,
  the step program's device time, the longest operations, and the idle
  gaps attributed to what the harness's own host spans say the host was
  doing.

What the reducer reads from a TPU trace: planes named ``/device:TPU:<n>``;
on each the line ``XLA Modules`` (one event per run of a compiled program)
and the line ``XLA Ops`` (one event per operation).  Host spans are the
events of any ``/host:`` plane whose name starts with ``cb:``: the harness
writes them with ``jax.profiler.TraceAnnotation`` and the profiler puts
both on one clock.

The window is taken on the device's own clock: from the start of the
first run of the step program (the module with the most device time) to
the start of its last run.  It holds whole step periods, so an idle share
does not depend on where the host happened to start and stop the tracer.
"""
import bisect
import collections
import re

DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "cb:"

Event = collections.namedtuple("Event", "plane line name start_ns dur_ns")


def read_xplane(path):
    """Adapter: the events the reducer needs, out of an .xplane.pb."""
    from jax.profiler import ProfileData
    events = []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith(DEVICE_PREFIX)
        on_host = plane.name.startswith(HOST_PREFIX)
        if not (on_device or on_host):
            continue
        for line in plane.lines:
            if on_device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            for ev in line.events:
                if on_host and not ev.name.startswith(SPAN_PREFIX):
                    continue
                events.append(Event(plane.name, line.name, ev.name,
                                    int(ev.start_ns), int(ev.duration_ns)))
    return events


def merge(intervals):
    """Union of [a, b) intervals as a sorted list of disjoint ones."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _module_key(name):
    # "jit_step(1234567890)" -> "jit_step": the fingerprint changes with
    # every change to the program, the name does not
    return name.split("(")[0]


_OP = re.compile(r"^(?P<lhs>\S+) = \(?(?P<type>[a-z0-9]+\[[0-9,]*\])?.*?"
                 r"[)}\]] (?P<op>[a-z][\w-]*)\(")


def op_kind(name):
    """An operation's kind out of the trace's name for it, which is the
    whole HLO line: ``%copy.39 = bf16[160,32]{...} copy(...)`` becomes
    ``copy bf16[160,32]``.  The 24 layers' copies of one shape then add up
    under one name instead of filling the list one by one."""
    m = _OP.match(name)
    if not m:
        return name.split(" = ")[0][:80]
    return ("%s %s" % (m.group("op"), m.group("type") or "")).strip()


def _label(a, b, spans, modules):
    """What the host was doing in the idle gap [a, b): the innermost host
    span over the gap's middle, and, where the step program runs inside
    that span, whether the gap lies before it, after it, inside a run of a
    program (the program waits on itself) or between two programs."""
    mid = 0.5 * (a + b)
    over = [s for s in spans if s[0] <= mid < s[1]]
    if not over:
        return "no_span"
    s0, s1, name = min(over, key=lambda s: s[1] - s[0])
    inside = [m for m in modules if s0 <= m[0] < s1]
    if inside:
        if any(m[0] <= mid < m[1] for m in inside):
            return name + ":in_program"
        if mid < inside[0][0]:
            return name + ":before_device"
        if mid >= inside[-1][1]:
            return name + ":after_device"
        return name + ":between_programs"
    return name


def reduce(events, top=10):
    """Reducer.  Returns None where no operation ran on a device; else a
    dict with ``window_s``, ``busy_s`` (averaged over the devices used),
    ``step_device_ms`` (median device time of one run of the step
    program), ``step_module``, ``steps``, ``device_ops`` and
    ``idle_gaps`` (each at most ``top`` pairs of name and seconds; an
    operation's name is its kind and result shape, with the number of
    distinct operations of that kind)."""
    events = [e if isinstance(e, Event) else Event(*e) for e in events]
    devices = sorted({e.plane for e in events
                      if e.plane.startswith(DEVICE_PREFIX)
                      and e.line == OPS_LINE})
    if not devices:
        return None
    spans = [(e.start_ns, e.start_ns + e.dur_ns, e.name[len(SPAN_PREFIX):])
             for e in events if e.plane.startswith(HOST_PREFIX)
             and e.name.startswith(SPAN_PREFIX)]

    busy, windows, step_ms, n_steps = [], [], [], []
    op_time = collections.Counter()
    gap_time = collections.Counter()
    op_names = {}
    step_module = None
    for dev in devices:
        mods = [e for e in events if e.plane == dev
                and e.line == MODULES_LINE]
        ops = [e for e in events if e.plane == dev and e.line == OPS_LINE]
        by_mod = collections.Counter()
        for m in mods:
            by_mod[_module_key(m.name)] += m.dur_ns
        if by_mod:
            step_module = by_mod.most_common(1)[0][0]
            runs = sorted((m.start_ns, m.start_ns + m.dur_ns) for m in mods
                          if _module_key(m.name) == step_module)
        else:
            runs = []
        if len(runs) >= 2:
            lo, hi = runs[0][0], runs[-1][0]
            runs = runs[:-1]
        else:       # a trace without two runs of a program: all of it
            lo = min(e.start_ns for e in ops)
            hi = max(e.start_ns + e.dur_ns for e in ops)
        if hi <= lo:
            continue
        merged = merge(_clip([(e.start_ns, e.start_ns + e.dur_ns)
                              for e in ops], lo, hi))
        busy.append(sum(b - a for a, b in merged))
        windows.append(hi - lo)
        if runs:
            step_ms.append(_median([b - a for a, b in runs]) / 1e6)
            n_steps.append(len(runs))
        for e in ops:
            a, b = max(e.start_ns, lo), min(e.start_ns + e.dur_ns, hi)
            if b > a:
                kind = op_kind(e.name)
                op_time[kind] += b - a
                op_names.setdefault(kind, set()).add(e.name)
        all_mods = sorted((m.start_ns, m.start_ns + m.dur_ns) for m in mods)
        # an idle gap is cut where a host span or a program begins or ends,
        # and each piece is labelled on its own
        cuts = sorted({x for s in spans for x in s[:2]}
                      | {x for m in all_mods for x in m})
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            inner = cuts[bisect.bisect_right(cuts, a):
                         bisect.bisect_left(cuts, b)]
            pieces = [a] + inner + [b]
            for x, y in zip(pieces[:-1], pieces[1:]):
                if y > x:
                    gap_time[_label(x, y, spans, all_mods)] += y - x
    if not windows:
        return None
    n = float(len(windows))
    return {
        "window_s": sum(windows) / n / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "step_device_ms": (sum(step_ms) / len(step_ms)) if step_ms else None,
        "step_module": step_module,
        "steps": int(sum(n_steps) / len(n_steps)) if n_steps else 0,
        "device_ops": [["%s x%d" % (k, len(op_names[k])), v / n / 1e9]
                       for k, v in op_time.most_common(top)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in gap_time.most_common(top)],
    }
