"""What the OS did with a step's thread, in the program's spans (ISSUE 38).

* ``profiler.span(name, os=True)`` / ``cpu=True``: the readings at both
  ends, cumulative; a busy loop is thread CPU and a sleep is not; a
  source the platform lacks leaves None; a plain span is what it was;
* the serving engine's step and phases carry them at both pipeline
  depths; the trainer's step is a ``train.step`` span a dispatch and
  still the jitted function to everything else;
* ``profiler.stalls`` on a ring written by hand: one planted stall of
  each kind of docs/observability.md "Reading a stall".
"""
import collections
import time

import numpy as np
import pytest
from test_spans import PHASES, _children, _engine, _mark, _since, \
    _submit_two

import jax

from mxnet_tpu import profiler

N_OS = len(profiler.OS_FIELDS)


# ---------------------------------------------------------------------------
# the readings
# ---------------------------------------------------------------------------

def _busy(seconds):
    t = time.perf_counter()
    while time.perf_counter() - t < seconds:
        pass


@pytest.mark.parametrize("body, on_cpu", [(_busy, True), (time.sleep, False)],
                         ids=["busy_loop", "sleep"])
def test_os_span_carries_both_readings(body, on_cpu):
    mark = _mark()
    with profiler.span("outer", os=True, rows=3):
        with profiler.span("work", os=True):
            body(0.05)
    got = {s.name: s for s in _since(mark)}
    work, outer = got["work"], got["outer"]
    assert set(work.args) == {"os0", "os1"}
    assert set(outer.args) == {"rows", "os0", "os1"}
    for s in (work, outer):
        a, b = s.args["os0"], s.args["os1"]
        assert len(a) == len(b) == N_OS
        assert all(x is not None for x in a + b)    # Linux has them all
        assert all(x <= y for x, y in zip(a, b))    # cumulative
    # cumulative across spans too: the inner's lie between the outer's
    for i in range(N_OS):
        assert outer.args["os0"][i] <= work.args["os0"][i] \
            <= work.args["os1"][i] <= outer.args["os1"][i]
    cpu = work.args["os1"][0] - work.args["os0"][0]
    wall = work.t1 - work.t0
    assert wall >= 0.05
    assert (cpu > 0.6 * wall) if on_cpu else (cpu < 0.2 * wall)
    # the process's clock holds the thread's
    assert work.args["os1"][1] - work.args["os0"][1] >= 0.9 * cpu
    if not on_cpu:      # a sleep is a voluntary switch
        assert work.args["os1"][2] > work.args["os0"][2]


def test_cpu_span_carries_the_two_clocks_only():
    mark = _mark()
    with profiler.span("phase", cpu=True, rows=1):
        _busy(0.02)
    (s,) = _since(mark)
    assert set(s.args) == {"rows", "cpu0", "cpu1"}
    a, b = s.args["cpu0"], s.args["cpu1"]
    assert len(a) == len(b) == 2
    assert b[0] - a[0] > 0.01 and b[1] - a[1] > 0.01
    # the same clocks, in the same places, as the full reading's first two
    assert profiler.OS_FIELDS[:2] == ("thread_cpu_s", "process_cpu_s")
    with profiler.span("full", os=True) as full:
        pass
    assert b[0] <= full.args["os0"][0] and b[1] <= full.args["os0"][1]


def test_plain_span_reads_nothing(monkeypatch):
    taken = []
    monkeypatch.setattr(profiler.span, "_READS", {
        k: (lambda k=k: taken.append(k),) + v[1:]
        for k, v in profiler.span._READS.items()})
    mark = _mark()
    with profiler.span("plain", rows=3) as s:
        s.set(late=7)
    (got,) = _since(mark)
    assert got.args == {"rows": 3, "late": 7} and taken == []
    with profiler.span("reads", cpu=True):
        pass
    with profiler.span("reads", os=True):
        pass
    assert taken == ["cpu", "cpu", "os", "os"]


def test_readings_stay_out_of_the_trace_annotation(monkeypatch):
    seen = {}

    class Annotation:
        def __init__(self, name, **kw):
            seen[name] = dict(kw)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kw):
            seen.setdefault("set", {}).update(kw)

    monkeypatch.setattr(profiler, "TraceAnnotation", Annotation)
    with profiler.span("step", os=True, step=4) as s:
        s.set(decode=2)
    with profiler.span("phase", cpu=True):
        pass
    assert seen == {"cb:step": {"step": 4}, "set": {"decode": 2},
                    "cb:phase": {}}


def _none_at(monkeypatch, source):
    """Take one source of the readings away; returns the fields that
    must then read None."""
    if source == "thread_time":
        monkeypatch.delattr(time, "thread_time")
        return {0}
    if source == "process_time":
        monkeypatch.setattr(time, "process_time",
                            lambda: (_ for _ in ()).throw(OSError()))
        return {1}
    if source == "resource":
        monkeypatch.setattr(profiler, "resource", None)
        return {2, 3, 4, 5}
    if source == "rusage_thread":       # a Unix without RUSAGE_THREAD
        monkeypatch.delattr(profiler.resource, "RUSAGE_THREAD")
        return {2, 3, 4, 5}
    real_open = open

    def no_proc(path, *a, **kw):
        if str(path).startswith("/proc/"):
            raise FileNotFoundError(path)
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", no_proc)
    monkeypatch.setattr(profiler, "_schedstat", profiler.threading.local())
    return {6}


@pytest.mark.parametrize("source", ["thread_time", "process_time",
                                    "resource", "rusage_thread",
                                    "schedstat"])
def test_missing_source_leaves_none(monkeypatch, source):
    missing = _none_at(monkeypatch, source)
    mark = _mark()
    with profiler.span("step", os=True):
        with profiler.span("phase", cpu=True):
            pass
    with profiler.span("step", os=True):    # schedstat: asked once a thread
        pass
    got = _since(mark)
    monkeypatch.undo()
    for s in got:
        for key in ("os0", "os1", "cpu0", "cpu1"):
            reading = s.args.get(key)
            if reading is None:
                continue
            for i, value in enumerate(reading):
                assert (value is None) == (i in missing), (s.name, key, i)
    # and the reader takes what is left
    report = profiler._os_delta(got[1].args["os0"], got[1].args["os1"], 1.0)
    assert report["wall_s"] == 1.0
    assert (report["cpu_s"] is None) == (0 in missing)
    assert (report["others_cpu_s"] is None) == bool(missing & {0, 1})
    assert (report["runq_wait_s"] is None) == (6 in missing)


def test_schedstat_is_opened_once_a_thread(monkeypatch):
    opened = []
    real_open = open

    def counting(path, *a, **kw):
        opened.append(path)
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", counting)
    monkeypatch.setattr(profiler, "_schedstat", profiler.threading.local())
    for _ in range(3):
        with profiler.span("step", os=True):
            pass
    assert opened == ["/proc/thread-self/schedstat"]

    def other():
        with profiler.span("step", os=True) as s:
            pass
        seen.append(s.args["os1"][6])

    seen = []
    t = profiler.threading.Thread(target=other)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    assert len(opened) == 2 and seen[0] is not None and seen[0] >= 0.0


# ---------------------------------------------------------------------------
# the engine's step, the trainer's step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("overlap", [False, True],
                         ids=["serial", "overlap"])
def test_engine_step_and_phases_carry_the_readings(overlap):
    eng = _engine(overlap=overlap)
    _submit_two(eng)
    eng.step()
    mark = _mark()
    for _ in range(3):
        assert eng.step() is not False
    spans = _since(mark)
    eng.run()
    eng.close()
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(steps) == 3
    for st in steps:
        assert len(st.args["os0"]) == len(st.args["os1"]) == N_OS
        kids = _children(spans, st)
        assert [k.name for k in kids] == PHASES
        # the wait alone carries the two clocks (a kernel call costs
        # 5.8 us on the chip's host: PERF.md section 6, PR 38), and they
        # run on from the step's first reading to its last
        for k in kids:
            assert set(k.args) == ({"cpu0", "cpu1"}
                                   if k.name == "engine.wait" else set())
        wait = kids[3].args
        for i in (0, 1):
            assert st.args["os0"][i] <= wait["cpu0"][i] <= wait["cpu1"][i] \
                <= st.args["os1"][i]
    # between two steps: the end of one reads no later than the start of
    # the next
    for a, b in zip(steps, steps[1:]):
        assert all(x <= y for x, y in zip(a.args["os1"], b.args["os0"]))
    # nothing stalled in three steps, or the report says where
    for r in profiler.stalls("engine.step", since=steps[0].t0):
        assert r["where"] in PHASES + ["self", "between"]


def _tiny_train(scan_steps=None):
    from mxnet_tpu.models import transformer as T
    cfg = T.TransformerConfig(
        vocab_size=64, max_len=16, d_model=32, n_heads=2, n_layers=1,
        d_ff=64, dropout=0.0, dtype="float32", param_dtype="float32",
        use_flash=False, remat=False)
    init_state, step = T.make_train_step(cfg, scan_steps=scan_steps)
    tokens = np.arange(32, dtype=np.int32).reshape(2, 16) % 64
    batch = {"tokens": tokens,
             "labels": np.where(tokens % 5 == 0, tokens, -100)}
    return init_state(jax.random.PRNGKey(0)), step, batch


@pytest.mark.parametrize("scan_steps", [None, 2], ids=["step", "scan2"])
def test_train_step_is_one_span_a_dispatch(scan_steps):
    state, step, batch = _tiny_train(scan_steps)
    rng = jax.random.PRNGKey(1)
    lowered = step.lower(state, batch, rng)      # still the jitted step's
    assert "stablehlo" in lowered.as_text() or "func.func" in lowered.as_text()
    mark = _mark()
    for _ in range(3):
        state, loss = step(state, batch, rng)
    jax.block_until_ready(loss)
    spans = [s for s in _since(mark) if s.name == "train.step"]
    assert len(spans) == 3 and all(s.parent == 0 for s in spans)
    first = spans[0].args["step"]
    assert [s.args["step"] for s in spans] == [first, first + 1, first + 2]
    for s in spans:
        want = {"step", "os0", "os1"} | ({"steps"} if scan_steps else set())
        assert set(s.args) == want
        assert s.args.get("steps") == scan_steps
        assert len(s.args["os0"]) == len(s.args["os1"]) == N_OS
    assert np.shape(loss) == (() if scan_steps is None else (scan_steps,))
    # one compiled program, the jitted function's own cache
    assert step._cache_size() == 1
    assert profiler.stalls("train.step", since=spans[0].t0) is not None


# ---------------------------------------------------------------------------
# profiler.stalls on a ring written by hand
# ---------------------------------------------------------------------------

MS = 1e-3
HOST = {"engine.plan": 0.4, "engine.stage": 1.0, "engine.launch": 0.5,
        "engine.commit": 0.3}
WAIT = 7.0          # ms; with 0.1 ms of the step in no phase and 0.7
BETWEEN = 0.7       # between two steps, a turn lasts 10 ms


class Ring:
    """Steps of 10 ms, each phase's thread on a CPU for all of its wall
    time but the wait's (0.05 ms of 7), nothing else running; ``step(...)``
    plants one turn's departures: ``wall`` and ``cpu`` by part (ms; the
    phases, ``"self"``, ``"between"``), ``others`` (the other threads'
    CPU, ms, by part), and counts booked to the step or to what follows
    it (``invol``, ``vol``, ``minor``, ``runq`` ms; ``*_after``)."""

    def __init__(self, read=("engine.wait",)):
        self.read = read        # the phases that carry ``cpu0`` / ``cpu1``
        self.spans, self.ids = [], iter(range(1, 100000))
        self.t, self.n = 100.0, 0
        # thread CPU, process CPU, vol, invol, minor, major, run-queue wait
        self.os = [5.0, 9.0, 10, 20, 3000, 4, 0.25]

    def _spend(self, part, wall, cpu, others):
        default = 0.05 if part == "engine.wait" else None
        w = wall.get(part, {"engine.wait": WAIT, "self": 0.1,
                            "between": BETWEEN}.get(part) or HOST.get(part))
        c = cpu.get(part, w if default is None else default)
        self.t += w * MS
        self.os[0] += c * MS
        self.os[1] += (c + others.get(part, 0.0)) * MS

    def _phase(self, name, parent, wall, cpu, others):
        t0, cpu0 = self.t, tuple(self.os[:2])
        self._spend(name, wall, cpu, others)
        self.spans.append(profiler.Span(
            next(self.ids), parent, name, t0, self.t, 1,
            {"cpu0": cpu0, "cpu1": tuple(self.os[:2])}
            if name in self.read else {}))

    def step(self, wall=(), cpu=(), others=(), **counts):
        wall, cpu, others = dict(wall), dict(cpu), dict(others)
        sid, t0, os0 = next(self.ids), self.t, tuple(self.os)
        for name in PHASES[:4]:
            self._phase(name, sid, wall, cpu, others)
        self._spend("self", wall, cpu, others)
        self._phase(PHASES[4], sid, wall, cpu, others)
        for i, key in ((2, "vol"), (3, "invol"), (4, "minor")):
            self.os[i] += counts.get(key, 0)
        if counts.get("runq", 0.0) is None:     # no such file from here on
            self.os[6] = None
        elif self.os[6] is not None:
            self.os[6] += counts.get("runq", 0.0) * MS
        self.spans.append(profiler.Span(
            sid, 0, "engine.step", t0, self.t, 1,
            {"step": self.n, "os0": os0, "os1": tuple(self.os)}))
        self.n += 1
        self._spend("between", wall, cpu, others)
        for i, key in ((2, "vol_after"), (3, "invol_after"),
                       (4, "minor_after")):
            self.os[i] += counts.get(key, 0)
        if self.os[6] is not None:
            self.os[6] += counts.get("runq_after", 0.0) * MS

    def steps(self, n):
        for _ in range(n):
            self.step()
        return self


#: kind: (the planted turn, the turn after it, what the report must say)
PLANTED = {
    "device_late": (dict(wall={"engine.wait": 157.0}), {},
                    dict(where="engine.wait", cause="device_late",
                         runtime="idle")),
    "readback_late": (dict(wall={"engine.wait": 1507.0}),
                      dict(wall={"engine.wait": 0.4}),
                      dict(where="engine.wait", cause="readback_late",
                           runtime="idle")),
    "readback_late_runtime_busy": (
        dict(wall={"engine.wait": 1507.0}, others={"engine.wait": 1400.0}),
        dict(wall={"engine.wait": 0.4}),
        dict(where="engine.wait", cause="readback_late", runtime="busy")),
    "host_ran_mapping_memory": (
        dict(wall={"engine.launch": 55.5}, minor=14000), {},
        dict(where="engine.launch", cause="host_ran", runtime="idle")),
    "host_preempted": (
        dict(wall={"engine.stage": 121.0}, cpu={"engine.stage": 1.0},
             invol=3, runq=118.0), {},
        dict(where="engine.stage", cause="host_preempted", runtime="idle")),
    "host_blocked": (
        dict(wall={"engine.plan": 150.4}, cpu={"engine.plan": 0.4}, vol=1,
             others={"engine.plan": 149.0}), {},
        dict(where="engine.plan", cause="host_blocked", runtime="busy")),
    "between_steps_harness_ran": (
        dict(wall={"between": 180.7}), {},
        dict(where="between", cause="host_ran", runtime="idle")),
    "between_steps_preempted": (
        dict(wall={"between": 180.7}, cpu={"between": 0.7}, invol_after=2,
             runq_after=175.0), {},
        dict(where="between", cause="host_preempted", runtime="idle")),
    "off_cpu_no_run_queue_reading": (
        dict(wall={"engine.commit": 110.3}, cpu={"engine.commit": 0.3},
             runq=None), {},
        dict(where="engine.commit", cause="host_off_cpu", runtime="idle")),
    "in_no_phase": (
        dict(wall={"self": 60.1}, cpu={"self": 0.1}, vol=1), {},
        dict(where="self", cause="host_blocked", runtime="idle")),
}


@pytest.mark.parametrize("read", [("engine.wait",), tuple(PHASES)],
                         ids=["wait_reads", "all_phases_read"])
@pytest.mark.parametrize("kind", list(PLANTED))
def test_stalls_names_the_planted_cause(kind, read, monkeypatch):
    """As the engine has it, the wait alone carries the clocks and a host
    phase's verdict rests on the step's CPU outside the wait; with every
    phase read, on that phase's own."""
    planted, after, want = PLANTED[kind]
    ring = Ring(read).steps(20)
    ring.step(**planted)
    ring.step(**after)
    ring.steps(20)
    monkeypatch.setattr(profiler, "_spans",
                        collections.deque(ring.spans, maxlen=65536))
    (r,) = profiler.stalls()
    assert r["args"] == {"step": 20}
    assert {k: r[k] for k in want} == want
    part, grown = next(iter({**planted.get("wall", {})}.items()))
    excess = grown - {"engine.wait": WAIT, "self": 0.1,
                      "between": BETWEEN, **HOST}[part]
    assert r["median_turn_s"] == pytest.approx(10 * MS)
    assert r["excess_s"] == pytest.approx(excess * MS)
    assert r["turn_s"] == pytest.approx((10 + excess) * MS)
    assert r["span_s"] + r["after"]["wall_s"] == pytest.approx(r["turn_s"])
    assert r["median_wait_s"] == pytest.approx(WAIT * MS)
    assert r["next_wait_s"] == pytest.approx(
        after.get("wall", {}).get("engine.wait", WAIT) * MS)
    if part in PHASES:
        assert r["children"][part]["excess_s"] == pytest.approx(excess * MS)
        assert set(r["children"]) == set(PHASES)
        assert {k for k, v in r["children"].items() if "cpu_s" in v} \
            == set(read)
    # the counts land where they were booked
    assert r["in_span"]["minor_faults"] == planted.get("minor", 0)
    assert r["in_span"]["invol_switches"] == planted.get("invol", 0)
    assert r["after"]["invol_switches"] == planted.get("invol_after", 0)
    if planted.get("runq", 0.0) is None:
        assert r["in_span"]["runq_wait_s"] is None
    else:
        assert r["in_span"]["runq_wait_s"] == pytest.approx(
            planted.get("runq", 0.0) * MS)
    on_cpu = sum(planted.get("cpu", {}).get(p, planted["wall"].get(p, w))
                 for p, w in HOST.items()) + 0.05 \
        + planted.get("cpu", {}).get("self",
                                     planted["wall"].get("self", 0.1))
    assert r["in_span"]["cpu_s"] == pytest.approx(on_cpu * MS)
    assert r["in_span"]["offcpu_s"] == pytest.approx(
        r["span_s"] - on_cpu * MS)


def test_stalls_window_factor_and_plain_spans(monkeypatch):
    ring = Ring().steps(10)
    ring.step(wall={"engine.wait": 12.5})       # 15.5 ms: over 1.5 medians
    ring.steps(10)
    ring.step(wall={"engine.stage": 9.0})       # 18 ms
    ring.steps(10)
    monkeypatch.setattr(profiler, "_spans",
                        collections.deque(ring.spans, maxlen=65536))
    assert [r["args"]["step"] for r in profiler.stalls()] == [10, 21]
    assert [r["args"]["step"] for r in profiler.stalls(factor=1.7)] == [21]
    late = ring.spans[-1].t0 - 150 * MS
    assert [r["args"]["step"] for r in profiler.stalls(since=late)] == [21]
    assert profiler.stalls("train.step") == []
    assert profiler.stalls(since=1e9) == []
    # the same ring without a reading: where and the wait's verdict still,
    # no more
    bare = [s._replace(args={k: v for k, v in s.args.items()
                             if k == "step"}) for s in ring.spans]
    monkeypatch.setattr(profiler, "_spans",
                        collections.deque(bare, maxlen=65536))
    a, b = profiler.stalls()
    assert (a["where"], a["cause"], a["runtime"]) == \
        ("engine.wait", "device_late", None)
    assert (b["where"], b["cause"], b["runtime"]) == \
        ("engine.stage", "host", None)
    assert a["in_span"] == {"wall_s": pytest.approx(14.8 * MS)}
