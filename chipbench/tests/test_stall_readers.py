"""The readers of what a stall was (``stall_readers.py``), on a ring of
spans written by hand that holds one stall of each kind -- the device late,
the read-back late (the next wait collapsed), the host off a CPU, the host
on one, a pause between two steps -- and one of training turns; on rings
without readings; and in one rehearsal run a cell."""
import json

import pytest

import run
import stall_readers as st

SERVE = ["turn_stall_max_ms.serve", "stall_offcpu_share.serve",
         "stall_host_late_share.serve", "stall_runtime_busy_share.serve"]
TRAIN = ["step_stall_share.train", "turn_stall_max_ms.train"]
CELLS = [w["name"] for w in run.read_json(run.ROOT, "BENCHMARK.json")
         ["workloads"]]
TRAIN_CELL = "bert_base.pretrain_s512"
MS = 1e-3
PHASES = (("engine.plan", 1.0), ("engine.stage", 0.5), ("engine.launch", 0.5),
          ("engine.wait", 7.0), ("engine.commit", 0.8))
#: step -> what departs in its turn (ms): a phase's wall, ``cpu`` of that
#: phase where the thread was not on a CPU for all of it, ``others`` the
#: other threads' CPU during it
PLANTED = {
    5: {"engine.wait": 157.0},                         # the device late
    12: {"engine.wait": 1507.0, "others": 600.0},      # the read-back late:
    13: {"engine.wait": 0.4},                          # ... the next collapsed
    20: {"engine.stage": 100.5, "cpu": 0.5},           # the host off a CPU
    27: {"engine.launch": 50.5},                       # the host on one
    33: {"between": 200.1, "cpu": 0.1},                # between two steps
}
EXCESS = {5: 150.0, 12: 1500.0, 20: 100.0, 27: 50.0, 33: 200.0}
N = 40
WINDOW_MS = N * 10.0 + sum(EXCESS.values()) - 6.6


def ring(readings=True, planted=PLANTED):
    """``N`` steps of 10 ms from t = 1 s: the phases above, 0.1 ms of the
    step in no phase, 0.1 ms between two steps; the thread on a CPU all
    the time but in the wait (0.05 ms of it).  Readings as the program
    keeps them: ``os0`` / ``os1`` on the step, ``cpu0`` / ``cpu1`` on the
    wait, cumulative.  Returns the spans and the window's ends."""
    spans, ids = [], iter(range(1, 10000))
    t = 1.0
    os = [3.0, 8.0, 100, 50, 9000, 2, 0.5]
    # a step before the window, an unrelated span
    spans.append((next(ids), 0, "engine.step", 0.5, 0.51, 1,
                  {"os0": tuple(os), "os1": tuple(os)} if readings else {}))
    spans.append((next(ids), 0, "data_loading", 1.0, 1.05, 3, {}))

    def spend(wall, cpu, others=0.0):
        nonlocal t
        t += wall * MS
        os[0] += cpu * MS
        os[1] += (cpu + others) * MS

    for i in range(N):
        plant = planted.get(i, {})
        step, t0, os0 = next(ids), t, tuple(os)
        for name, wall in PHASES:
            wall = plant.get(name, wall)
            mine = name in plant and name != "engine.wait"
            cpu = 0.05 if name == "engine.wait" \
                else plant.get("cpu", wall) if mine else wall
            others = plant.get("others", 0.0) if name in plant else 0.0
            a, cpu0 = t, tuple(os[:2])
            spend(wall, cpu, others)
            args = {"cpu0": cpu0, "cpu1": tuple(os[:2])} \
                if readings and name == "engine.wait" else {}
            spans.append((next(ids), step, name, a, t, 1, args))
        spend(0.1, 0.1)
        args = {"step": i}
        if readings:
            args.update(os0=os0, os1=tuple(os))
        spans.append((step, 0, "engine.step", t0, t, 1, args))
        between = plant.get("between", 0.1)
        spend(between, plant.get("cpu", between) if "between" in plant
              else between)
    return spans, 1.0, t


def train_ring(turns_ms, close_ms=50.0):
    """``train.step`` spans of 2 ms (the dispatch), each turn as long as
    given; the window ends ``close_ms`` after the last one's start (the
    wait for the last state)."""
    spans, t = [], 10.0
    os = [1.0, 2.0, 0, 0, 0, 0, 0.0]
    for i, turn in enumerate(turns_ms):
        spans.append((i + 1, 0, "train.step", t, t + 2 * MS, 1,
                      {"step": i, "os0": tuple(os), "os1": tuple(os)}))
        t += turn * MS
    return spans, 10.0, t - turns_ms[-1] * MS + close_ms * MS


def reader(name):
    return run.load_module("layer_metrics", name).read


def read_all(names, window, counters=None):
    return {name: reader(name)(None, window, counters or {}, None)
            for name in names}


@pytest.fixture
def serve(monkeypatch):
    spans, t0, t1 = ring()
    monkeypatch.setattr(st.span_readers, "recent_spans", lambda: spans)
    return {"t0": t0, "t1": t1}


def test_the_window_and_its_turns(serve):
    assert serve["t1"] - serve["t0"] == pytest.approx(WINDOW_MS * MS)
    turns = st.window_turns(serve)
    assert len(turns) == N
    for i, turn in enumerate(turns):
        want = 10.0 + EXCESS.get(i, -6.6 if i == 13 else 0.0)
        assert turn["turn_s"] == pytest.approx(want * MS), i
        assert turn["wait_s"] == pytest.approx(
            PLANTED.get(i, {}).get("engine.wait", 7.0) * MS)
        assert turn["wait_cpu_s"] == pytest.approx(0.05 * MS)
    assert turns[12]["next_wait_s"] == pytest.approx(0.4 * MS)
    assert turns[12]["wait_others_s"] == pytest.approx(600 * MS)
    assert turns[-1]["next_wait_s"] is None
    assert sorted(round(e / MS) for _, e in st.stalled(turns)) == \
        sorted(EXCESS.values())
    # the thread's CPU over a turn: all of it but the wait and what the
    # planted turns slept
    assert turns[0]["cpu_s"] == pytest.approx(3.05 * MS)
    assert turns[20]["cpu_s"] == pytest.approx(3.05 * MS)
    assert turns[27]["cpu_s"] == pytest.approx(53.05 * MS)
    assert turns[33]["cpu_s"] == pytest.approx(3.05 * MS)


def test_each_metric_on_the_planted_ring(serve):
    got = read_all(SERVE, serve, {"overlap_steps": N, "steps": N})
    # one hole of 1.5 s, not the 2.0 s that stalled in all
    assert got["turn_stall_max_ms.serve"] == pytest.approx(1500.0)
    # host time grew by 100 (off a CPU), 50 (on one), 200 (off, between
    # two steps); the waits' 1,650 ms are no host time
    assert got["stall_offcpu_share.serve"] == pytest.approx(100 * 300 / 350.)
    # of 150 + 1,500 ms inside the wait, the 1,500 were followed by a
    # collapsed wait
    assert got["stall_host_late_share.serve"] == \
        pytest.approx(100 * 1500 / 1650.)
    # and during 600 of them the other threads were on a CPU
    assert got["stall_runtime_busy_share.serve"] == \
        pytest.approx(100 * 600 / 1650.)
    json.dumps(got)


@pytest.mark.parametrize("kind, want", [
    ("device_late", (150.0, 0.0, 0.0, 0.0)),
    ("host_late", (1500.0, 0.0, 100.0, 40.0)),
    ("host_off_cpu", (100.0, 100.0, 0.0, 0.0)),
    ("host_on_cpu", (50.0, 0.0, 0.0, 0.0)),
    ("between_steps", (200.0, 100.0, 0.0, 0.0)),
    ("none", (0.0, 0.0, 0.0, 0.0)),
])
def test_one_stall_at_a_time(monkeypatch, kind, want):
    steps = {"device_late": (5,), "host_late": (12, 13),
             "host_off_cpu": (20,), "host_on_cpu": (27,),
             "between_steps": (33,), "none": ()}[kind]
    spans, t0, t1 = ring(planted={i: PLANTED[i] for i in steps})
    monkeypatch.setattr(st.span_readers, "recent_spans", lambda: spans)
    got = read_all(SERVE, {"t0": t0, "t1": t1}, {"overlap_steps": N})
    assert tuple(got[name] for name in SERVE) == pytest.approx(want)


def test_depth_zero_has_no_step_to_run_ahead(serve):
    for counters in ({}, {"overlap_steps": 0, "steps": N}):
        got = read_all(SERVE, serve, counters)
        assert got["stall_host_late_share.serve"] is None
        assert got["turn_stall_max_ms.serve"] == pytest.approx(1500.0)


def test_window_cuts_the_ring(serve):
    # steps 0..4: the first stall starts at 1.050
    early = {"t0": 1.0, "t1": 1.0495}
    assert len(st.window_turns(early)) == 5
    got = read_all(SERVE, early, {"overlap_steps": 5})
    assert all(v == 0.0 for v in got.values()), got
    # the last turn of a window ends where the window does: the stalled
    # step, begun at 1.050, in a window that ends 40 ms later
    cut = {"t0": 1.0, "t1": 1.090}
    turns = st.window_turns(cut)
    assert len(turns) == 6
    assert turns[-1]["turn_s"] == pytest.approx(40 * MS)
    assert reader(SERVE[0])(None, cut, {}, None) == pytest.approx(30.0)
    # no step began here
    for name in SERVE + TRAIN:
        assert reader(name)(None, {"t0": 50.0, "t1": 60.0},
                            {"overlap_steps": 1}, None) is None


def test_training_turns(monkeypatch):
    turns = [52.0] * 10 + [3652.0] + [52.0] * 10 + [152.0] + [52.0] * 5
    spans, t0, t1 = train_ring(turns, close_ms=53.0)
    monkeypatch.setattr(st.span_readers, "recent_spans", lambda: spans)
    window = {"t0": t0, "t1": t1}
    got = read_all(TRAIN, window)
    seconds = (sum(turns) - 52.0 + 53.0) * MS
    assert t1 - t0 == pytest.approx(seconds)
    assert got["turn_stall_max_ms.train"] == pytest.approx(3600.0)
    assert got["step_stall_share.train"] == \
        pytest.approx(100 * 3.7 / seconds)
    # the wait that closes the window is the last turn's: a pause in it
    # shows (the median turn is 52 ms)
    late = {"t0": t0, "t1": t1 + 4.0}
    assert reader(TRAIN[1])(None, late, {}, None) == pytest.approx(4001.0)
    # the serving readers find no ``engine.step`` there, these no
    # ``train.step`` in a serving ring
    assert all(v is None for v in
               read_all(SERVE, window, {"overlap_steps": 1}).values())
    spans, t0, t1 = ring()
    monkeypatch.setattr(st.span_readers, "recent_spans", lambda: spans)
    assert all(v is None for v in
               read_all(TRAIN, {"t0": t0, "t1": t1}).values())


def test_a_ring_without_readings_gives_nothing(monkeypatch):
    """The parent commit: ``engine.step`` and its phases as plain spans,
    no ``train.step`` at all; and a program that keeps no ring."""
    spans, t0, t1 = ring(readings=False)
    monkeypatch.setattr(st.span_readers, "recent_spans", lambda: spans)
    window = {"t0": t0, "t1": t1}
    got = read_all(SERVE + TRAIN, window, {"overlap_steps": N})
    assert all(v is None for v in got.values()), got
    monkeypatch.setattr(st.span_readers, "recent_spans", lambda: None)
    got = read_all(SERVE + TRAIN, window, {"overlap_steps": N})
    assert all(v is None for v in got.values()), got


def test_benchmark_json_lists_the_six_last():
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    tail = bench["per_layer"][-6:]
    assert [m["name"] for m in tail] == SERVE + TRAIN
    serving = [c for c in CELLS if c != TRAIN_CELL]
    for m in tail:
        assert m["source"] == "program_span" and m["better"] == "lower"
        train = m["name"].endswith(".train")
        assert m["workloads"] == ([TRAIN_CELL] if train else serving)
        assert m["layer"] == ("model step" if train else "engine")
        assert m["moves"] == ("train_tok_s" if train else "serve_tok_s")
        assert m["unit"] == ("ms" if "_ms." in m["name"] else "%")
        moved = next(e for e in bench["end_to_end"]
                     if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved["workloads"])
    for cell in CELLS:
        reported = {m["name"] for m in run.metrics_for(
            {"name": cell, "bench": bench}, "per_layer")}
        mine = TRAIN if cell == TRAIN_CELL else SERVE
        assert reported & set(SERVE + TRAIN) == set(mine)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_reads_the_programs_own_ring(capsys, cell):
    """One ``--rehearse --trace 1`` run: the line's ``metrics`` stay empty
    (a rehearsal writes no device metric), and the readers find the
    window's turns in the ring the run left."""
    capsys.readouterr()
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 38),
                   "--seconds", "1", "--trace", "1", "--rehearse"])
    out, _ = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}
    name = st.TRAIN_STEP if cell == TRAIN_CELL else st.span_readers.STEP
    spans = [s for s in st.span_readers.recent_spans() if s[2] == name]
    assert spans and all("os0" in s[6] and "os1" in s[6] for s in spans)
    window = {"t0": spans[0][3], "t1": spans[-1][4]}
    counters = {"overlap_steps": 0}
    for m in (TRAIN if cell == TRAIN_CELL else SERVE):
        value = reader(m)(None, window, counters, None)
        if m == "stall_host_late_share.serve":
            assert value is None        # a CPU engine runs at depth 0
        else:
            assert value is not None and value >= 0.0, m
