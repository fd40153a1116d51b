"""``profiler.span``: the one way a host span is made (ISSUE 26).

* the span itself: what the ring records, that the ring is bounded,
  that ``Task``/``Event``/``Marker``/``Counter`` dump as before and
  only while the profiler records;
* the serving engine's step phases, serial and pipelined, with
  ``metrics=False``;
* the same phases read back out of a ``jax.profiler`` trace;
* the named scopes of the two step programs: present in the lowered
  text, absent from what the compiler emits once metadata is stripped
  (the scopes cost nothing on the device).
"""
import glob
import json
import os
import re
import threading

import numpy as np
import pytest
from conftest import pools_seen_on

import jax

from mxnet_tpu import profiler

PHASES = ["engine.plan", "engine.stage", "engine.launch", "engine.wait",
          "engine.commit"]


def _since(mark):
    return [s for s in profiler.recent_spans() if s.id > mark]


def _mark():
    with profiler.span("mark") as m:
        pass
    return m.id


# ---------------------------------------------------------------------------
# the span
# ---------------------------------------------------------------------------

def test_span_records_name_times_parent_thread_args():
    mark = _mark()
    with profiler.span("outer", rows=3) as outer:
        with profiler.span("inner") as inner:
            inner.set(late=7)
    got = {s.name: s for s in _since(mark)}
    assert set(got) == {"outer", "inner"}
    o, i = got["outer"], got["inner"]
    assert o.t0 <= i.t0 <= i.t1 <= o.t1
    assert (o.t0, o.t1) == (outer.t0, outer.t1)
    assert i.parent == o.id and o.parent == 0
    assert o.thread == i.thread == threading.get_ident()
    assert o.args == {"rows": 3} and i.args == {"late": 7}
    # ended first, recorded first
    assert [s.name for s in _since(mark)] == ["inner", "outer"]


def test_span_parent_is_per_thread():
    mark = _mark()
    seen = {}

    def other():
        with profiler.span("other") as s:
            pass
        seen["parent"], seen["thread"] = s.parent, threading.get_ident()

    with profiler.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    got = {s.name: s for s in _since(mark)}
    assert seen["parent"] == 0 and got["other"].parent == 0
    assert got["other"].thread == seen["thread"] != got["main"].thread


def test_span_survives_an_exception_and_start_stop_out_of_order():
    mark = _mark()
    with pytest.raises(KeyError):
        with profiler.span("raises"):
            raise KeyError("x")
    a = profiler.Task("a").start()
    b = profiler.Task("b").start()
    a.stop()            # not nested: a ends before b
    b.stop()
    c = profiler.Task("c").start()
    t = threading.Thread(target=c.stop)     # ends on another thread
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    with profiler.span("after") as after:
        pass
    assert after.parent == 0       # nothing was left open
    assert [s.name for s in _since(mark)] == ["raises", "a", "b", "c",
                                              "after"]


def test_span_ring_is_bounded():
    cap = profiler._spans.maxlen
    assert cap == 65536
    for _ in range(cap + 10):
        with profiler.span("fill"):
            pass
    assert len(profiler.recent_spans()) == cap
    spans = profiler.recent_spans()
    spans.clear()                   # a copy: the ring is not touched
    assert len(profiler.recent_spans()) == cap


def test_scopes_dump_as_before_and_only_while_recording(tmp_path):
    """Task / Event / Marker / Counter reach the dump with the keys they
    had; outside set_state('run') they append nothing (they used to
    grow the event list of a process that never dumps)."""
    before = len(profiler._events)
    with profiler.Task("idle_task"):
        pass
    profiler.Marker("idle_marker").mark()
    profiler.Counter("idle_counter", 1).increment()
    assert len(profiler._events) == before

    profiler.set_config(filename=str(tmp_path / "scopes.json"))
    profiler.set_state("run")
    try:
        with profiler.Task("t"):
            pass
        e = profiler.Event("e").start()
        e.stop()
        with profiler.span("s", cat="operator", rows=2):
            pass
        profiler.Marker("m").mark()
    finally:
        profiler.set_state("stop")
    with open(profiler.dump()) as f:
        evs = {e["name"]: e for e in json.load(f)["traceEvents"]}
    assert evs["t"]["cat"] == "task" and evs["e"]["cat"] == "event"
    for name in ("t", "e"):
        assert set(evs[name]) == {"name", "ph", "ts", "dur", "pid", "tid",
                                  "cat"}
        assert evs[name]["ph"] == "X" and evs[name]["dur"] >= 0
    assert evs["s"]["cat"] == "operator" and evs["s"]["args"] == {"rows": 2}
    assert evs["m"]["ph"] == "i"
    # on the shared clock
    assert abs(evs["t"]["ts"] - profiler.now_us()) < 60e6


# ---------------------------------------------------------------------------
# the engine's phases
# ---------------------------------------------------------------------------

def _engine(**kw):
    from mxnet_tpu.models import gpt, transformer as T
    from mxnet_tpu.serving import ServingEngine
    cfg = gpt.gpt_tiny(use_flash=False, remat=False, dropout=0.0,
                       dtype="float32", vocab_size=128, max_len=64)
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    kw.setdefault("metrics", False)
    # ``overlap``: the TPU's schedule, by substituting what the engine
    # observes while it is built
    overlap = kw.pop("overlap", False)
    with pools_seen_on("tpu" if overlap else "cpu"):
        eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                            prefill_chunk=4, kernel="xla", **kw)
    assert eng.overlap is overlap
    return eng


def _submit_two(eng, new=6):
    rng = np.random.RandomState(0)
    for P in (5, 3):
        eng.submit(rng.randint(1, 90, P).astype(np.int32), new)


def _children(spans, root):
    """The spans below ``root`` on its own thread, by parent ids."""
    below, out = {root.id}, []
    for s in sorted(spans, key=lambda s: s.t0):
        if s.parent in below:
            below.add(s.id)
            out.append(s)
    return out


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["serial", "overlap"])
def test_engine_step_encloses_the_five_phases(overlap):
    eng = _engine(overlap=overlap)
    keys0 = set(eng.stats)
    _submit_two(eng)
    eng.step()                      # compiles; pipelined: nothing to drain
    mark = _mark()
    n_calls = 3
    for _ in range(n_calls):
        assert eng.step() is not False
    spans = _since(mark)
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(steps) == n_calls
    for st in steps:
        kids = _children(spans, st)
        assert [k.name for k in kids] == PHASES
        assert all(st.t0 <= k.t0 <= k.t1 <= st.t1 for k in kids)
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
        assert sum(k.t1 - k.t0 for k in kids) <= st.t1 - st.t0
        assert st.parent == 0
        # beside what the step says of its plan, what the OS says of
        # its thread (tests/test_stall_forensics.py)
        assert set(st.args) == {"step", "decode", "prefill", "dead",
                                "pages", "os0", "os1"}
        assert st.args["decode"] + st.args["prefill"] + st.args["dead"] \
            == eng.n_rows
        # the gather path (this engine's, on CPU) reads every row's
        # whole table
        assert st.args["pages"] == eng.n_rows * eng.pages_per_slot
    assert [s.args["step"] for s in steps] == \
        list(range(steps[0].args["step"], steps[0].args["step"] + n_calls))
    # the build is inline at either depth: every ``engine.plan`` is a
    # step's child on the caller's thread, none marked ``hidden``
    plans = [s for s in spans if s.name == "engine.plan"]
    assert len(plans) == n_calls
    assert all(s.thread == steps[0].thread and "hidden" not in s.args
               for s in plans)
    eng.run()
    eng.close()
    # no new key, all numeric: the benchmark's driver subtracts every one
    assert set(eng.stats) == keys0
    assert all(isinstance(v, (int, float)) for v in eng.stats.values())
    # a call that finds no work is no step
    n_steps = sum(s.name == "engine.step" for s in _since(mark))
    assert eng.step() is False
    assert sum(s.name == "engine.step" for s in _since(mark)) == n_steps


def test_engine_metrics_on_puts_serving_step_between(tmp_path):
    """With metrics on, the dispatch (stage and launch) lies under the
    ``serving_step`` operator span, which lies under ``engine.step``
    beside the other phases (at either depth: the read-back is the
    loop's, one step later at depth 1, not the operator's); a recording
    profiler dumps it as the cat-"operator" event it was."""
    eng = _engine(metrics=True)
    _submit_two(eng)
    eng.step()
    profiler.set_config(filename=str(tmp_path / "op.json"))
    profiler.set_state("run")
    mark = _mark()
    try:
        eng.step()
    finally:
        profiler.set_state("stop")
    spans = _since(mark)
    step = next(s for s in spans if s.name == "engine.step")
    kids = _children(spans, step)
    assert [k.name for k in kids] == [
        "engine.plan", "serving_step", "engine.stage", "engine.launch",
        "engine.wait", "engine.commit"]
    op = kids[1]
    assert op.parent == step.id
    assert {k.parent for k in kids[2:4]} == {op.id}
    assert {k.parent for k in kids[4:]} == {step.id}
    with open(profiler.dump()) as f:
        evs = json.load(f)["traceEvents"]
    ops = [e for e in evs if e["name"] == "serving_step"]
    assert len(ops) == 1 and ops[0]["cat"] == "operator"
    assert {e["name"] for e in evs if e.get("cat") == "span"} >= set(PHASES)
    eng.run()


def test_engine_phases_reach_the_device_trace(tmp_path):
    """Three steps under a jax.profiler session (started the library's
    own way, ``xla_profile=True``, beside the chrome-trace recording):
    the phases are events of a host plane of the .xplane.pb, under the
    prefix the benchmark's reducer filters host events by, and the
    chrome dump holds the same spans."""
    from jax.profiler import ProfileData
    eng = _engine()
    _submit_two(eng)
    eng.step()
    profiler.set_config(xla_profile=True, xla_trace_dir=str(tmp_path),
                        filename=str(tmp_path / "chrome.json"))
    profiler.set_state("run")       # jax.profiler.start_trace(tmp_path)
    try:
        for _ in range(3):
            eng.step()
    finally:
        profiler.set_state("stop")
        profiler.set_config(xla_profile=False)
    eng.run()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    names = {}
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(profiler.SPAN_PREFIX):
                    names[ev.name] = names.get(ev.name, 0) + 1
    assert profiler.SPAN_PREFIX == "cb:"
    for phase in ["engine.step"] + PHASES:
        assert names.get("cb:" + phase) == 3, names
    with open(profiler.dump()) as f:
        dumped = [e["name"] for e in json.load(f)["traceEvents"]]
    for phase in ["engine.step"] + PHASES:
        assert dumped.count(phase) == 3


# ---------------------------------------------------------------------------
# named scopes in the step programs
# ---------------------------------------------------------------------------

TRAIN_SCOPES = ["embed", "attn", "attn/qkv", "attn/scores", "attn/out",
                "ln1", "ffn", "ln2", "mlm_head", "loss", "optimizer"]
ENGINE_SCOPES = ["embed", "qkv", "kv_write", "paged_attn",
                 "paged_attn/gather", "paged_attn/attend", "attn_out",
                 "ffn", "head", "sample"]


def _train_step(T, use_flash=False):
    cfg = T.TransformerConfig(
        vocab_size=64, max_len=16, d_model=32, n_heads=2, n_layers=2,
        d_ff=64, dropout=0.0, dtype="bfloat16", param_dtype="float32",
        use_flash=use_flash, remat=False)
    init_state, step = T.make_train_step(cfg)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), np.int32),
             "labels": jax.ShapeDtypeStruct((2, 16), np.int32)}
    return step.lower(state, batch, jax.random.PRNGKey(0))


def _engine_step(E, G, T, kernel="xla"):
    cfg = G.gpt_tiny(use_flash=False, remat=False, dropout=0.0,
                     dtype="float32", vocab_size=128, max_len=64)
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(3), cfg))
    S, R, PP, ps = 2, 6, 4, 4
    fn = E._make_step(cfg, S, R, PP, ps, False, kernel=kernel)
    dh = cfg.d_model // cfg.n_heads
    pools = [{"kv": jax.ShapeDtypeStruct((S * PP + 1, ps, cfg.n_heads,
                                          2 * dh), np.float32)}
             for _ in range(cfg.n_layers)]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
    return fn.lower(params, pools, i32(R), i32(R), i32(R),
                    jax.ShapeDtypeStruct((R,), bool), i32(S + 1, PP),
                    i32(S, 1))


def _scope_paths(lowered):
    """The scope path of every operation of the lowered program, as
    the location carries it: ``jit(step)/transpose(jvp(attn))/qkv/dot``
    reads ``attn/qkv/dot`` (autodiff wraps the outermost scope's name in
    the transformation's; the backward pass keeps the forward's path)."""
    text = lowered.as_text(debug_info=True)
    paths = set()
    for loc in re.findall(r'loc\("(jit\([^"]*)"', text):
        parts = [re.sub(r"^(?:\w+\()+([^()]*)\)+$", r"\1", part)
                 for part in loc.split("/")
                 if not part.startswith(("jit(", "pjit"))]
        paths.add("/".join(parts))
    return text, paths


def _has_scope(paths, scope):
    return any(("/" + scope + "/") in ("/" + path + "/") for path in paths)


def test_train_step_scopes_in_lowered_text():
    from mxnet_tpu.models import transformer as T
    _, locs = _scope_paths(_train_step(T))
    for scope in TRAIN_SCOPES:
        assert _has_scope(locs, scope), scope
    # no layer index in a name: one path for both layers
    assert not any(re.search(r"attn[_.]?\d", loc) for loc in locs)


def test_engine_step_scopes_in_lowered_text():
    from mxnet_tpu.models import gpt as G, transformer as T
    from mxnet_tpu.serving import engine as E
    _, locs = _scope_paths(_engine_step(E, G, T))
    for scope in ENGINE_SCOPES:
        assert _has_scope(locs, scope), scope
    _, locs = _scope_paths(_engine_step(E, G, T, kernel="pallas"))
    assert _has_scope(locs, "paged_attn")


def test_flash_attention_scope_in_lowered_text():
    from mxnet_tpu.kernels.flash_attention import flash_attention
    q = jax.ShapeDtypeStruct((1, 16, 2, 8), np.float32)
    _, locs = _scope_paths(jax.jit(flash_attention).lower(q, q, q))
    assert _has_scope(locs, "flash_attn")


def _stripped(lowered):
    """What the compiler emits, without what only names things: the
    operations' metadata and the tables of files and stack frames it
    points into."""
    text = lowered.compile().as_text()
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:.+\n)*", "", text, flags=re.M)


def _without_scopes(monkeypatch):
    import contextlib
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())


@pytest.mark.parametrize("program", ["train", "engine"])
def test_scopes_change_nothing_the_compiler_emits(program, monkeypatch):
    """The compiled step programs with the scopes are, metadata
    stripped, the programs without them; and the training step, which
    callers dispatch through its ``train.step`` span, lowers to the text
    of the jitted function inside, the program of before the span."""
    from mxnet_tpu.models import gpt as G, transformer as T
    from mxnet_tpu.serving import engine as E

    def build():
        E._step_cache.clear()
        return _train_step(T) if program == "train" \
            else _engine_step(E, G, T)

    with_scopes = build()
    if program == "train":
        monkeypatch.setattr(T, "_SpannedStep", lambda jitted, **args: jitted)
        bare = build()
        monkeypatch.undo()
        assert with_scopes.as_text() == bare.as_text()
        assert _stripped(with_scopes) == _stripped(bare)
    assert "attn_out" in with_scopes.as_text(debug_info=True) \
        or "mlm_head" in with_scopes.as_text(debug_info=True)
    _without_scopes(monkeypatch)
    without = build()
    assert "mlm_head" not in without.as_text(debug_info=True)
    assert "attn_out" not in without.as_text(debug_info=True)
    assert "ENTRY" in _stripped(without)
    assert _stripped(with_scopes) == _stripped(without)
    E._step_cache.clear()
