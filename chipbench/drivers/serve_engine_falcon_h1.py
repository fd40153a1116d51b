"""Driver ``serve_engine_falcon_h1``: ``serve_engine`` for a configuration
of the Falcon-H1 family.  The same closed loop, clock, counters and
comparison; what differs is what names the model: the config object the
engine is given, the operations a token needs (``window["flops"]``), and
the reference's token array, sized by the longest sample (the position
table of this family has 262,144 entries) and scored one request at a
time (a request's float32 logits are 0.7 GB).  Every run also says on
standard error where its window's time went, turn by turn
(``turn_report``)."""
import gc
import importlib.util
import json
import os
import statistics
import sys
import time

import numpy as np

import model_math_falcon_h1
import span_readers
import tracing

_spec = importlib.util.spec_from_file_location(
    "chipbench_drivers_serve_engine",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "serve_engine.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)


class Session(base.Session):
    # a fault planted by calibration runs alone (``fault_readings``)
    fault = None

    def setup(self):
        from mxnet_tpu.models.falcon_h1 import FalconH1Config
        from mxnet_tpu.serving import ServingEngine

        c, e = self.config, self.config["engine"]
        self.cfg = FalconH1Config.from_hf(c, dtype=c["dtype"])
        self.params = self.cell["reference"].make_params(
            self.cell["seed"], c, c["param_dtype"])
        self.engine = ServingEngine(
            self.params, self.cfg, num_slots=e["num_slots"],
            page_size=e["page_size"], pages_per_slot=e["pages_per_slot"],
            prefill_chunk=e["prefill_chunk"], kv_int8=e["kv_int8"],
            prefix_cache=e["prefix_cache"], metrics=False,
            device=self.cell["devices"][0])
        if self.fault == "state_not_carried":
            _never_carry_state(self.engine)
        self.requests = self.cell["generator"].generate(
            self.traffic, c, self.cell["seed"])
        self.active, self.finished, self.submitted = {}, [], []
        self.step_ms, self.work, self.pumped = [], [0, 0, 0], []
        for prompt, new in self.requests.first():
            self._submit(prompt, new)
        for _ in range(self.traffic["warm_steps"]):
            self._turn(tracing.no_span)

    def _pump(self, seconds, span=tracing.no_span):
        work0 = list(self.work)
        out = super()._pump(seconds, span)
        self.pumped.append((work0, list(self.work)))
        return out

    def measure(self, seconds, trace=False):
        self.pumped = []
        collections = []          # (start, seconds) of full collections

        def collected(phase, info):
            if info["generation"] == 2:
                if phase == "start":
                    collections.append([time.perf_counter(), None])
                else:
                    collections[-1][1] = \
                        time.perf_counter() - collections[-1][0]
        gc.callbacks.append(collected)
        try:
            window = super().measure(seconds, trace)
        finally:
            gc.callbacks.remove(collected)
        # every run says on standard error where its window's time went,
        # so that a second lost in one is placed and not inferred
        window["turns"] = turn_report(window, collections)
        print("turns " + json.dumps(window["turns"]), file=sys.stderr,
              flush=True)
        # the window is the first pump (a traced slice follows it)
        rows, ctx, sampled = (b - a for a, b in zip(*self.pumped[0]))
        window["flops"] = model_math_falcon_h1.serve_flops(
            self.config, rows, ctx, sampled)
        return window

    def check(self):
        """``serve_engine``'s comparison, the sample's requests one at a
        time in an array as long as the longest of them."""
        c = self.config
        V = c["vocab_size"]
        bad = 0
        for client, new, prompt in self.served:
            bad += int(new.size != client.new
                       or not np.array_equal(prompt, client.prompt)
                       or new.min() < 0 or new.max() >= V)
        out = {"bad_answers": float(bad),
               "missing_answers": float(len(self.served) == 0)}
        if not self.served:
            return out
        sample = base.pick_sample(self.served,
                                  self.traffic["check_requests"],
                                  self.cell["seed"])
        T = max(new.size + prompt.size for _, new, prompt in sample)
        tokens = np.zeros((len(sample), T), np.int32)
        scored = np.zeros((len(sample), T), bool)
        for i, (client, new, prompt) in enumerate(sample):
            seq = np.concatenate([prompt, new])
            tokens[i, :seq.size] = seq
            scored[i, prompt.size - 1:seq.size - 1] = True
        self.checked = (tokens, scored)
        out["logit_gap"] = max(
            float(base.worst_gap(logits, tokens[i:i + 1], scored[i:i + 1]))
            for i, logits in _each_logits(self, "float32"))
        return out


def turn_report(window, collections, longest=5, slow_ms=20.0):
    """The measured window turn by turn, from the program's ring of spans
    (``engine.step`` and its phases, on the window's clock).  A turn runs
    from the start of one ``engine.step`` to the start of the next (the
    last to the window's end): the step, then whatever the host did
    outside it (the clients' side of the harness, a collection, a pause
    of the process).  Gives the sums inside and outside the steps, the
    steps with and without prompt rows apart (their number and median:
    a slower machine moves both medians, another mix of traffic the
    numbers), each phase's median, the ``longest`` turns with their
    phases, and every full collection of ``slow_ms`` or more.  None
    where the program keeps no spans."""
    spans = span_readers.recent_spans()
    t0, t1 = window["t0"], window["t1"]
    steps = sorted((s for s in spans or ()
                    if s[2] == span_readers.STEP and t0 <= s[3] < t1),
                   key=lambda s: s[3])
    if not steps:
        return None
    by_id = {s[0]: s for s in spans}
    phases = {s[0]: {} for s in steps}
    for s in spans:
        up = by_id.get(s[1]) if s[2] in span_readers.PHASES else None
        while up is not None and up[2] != span_readers.STEP:
            up = by_id.get(up[1])
        if up is not None and up[0] in phases:
            mine = phases[up[0]]
            mine[s[2]] = mine.get(s[2], 0.0) + 1e3 * (s[4] - s[3])
    starts = [s[3] for s in steps] + [t1]
    turns = [{"at_s": s[3] - t0, "turn_ms": 1e3 * (nxt - s[3]),
              "step_ms": 1e3 * (s[4] - s[3]),
              "outside_ms": 1e3 * (nxt - s[4]), "phases": phases[s[0]]}
             for s, nxt in zip(steps, starts[1:])]
    kinds = {}
    for s in steps:
        args = s[6] if len(s) > 6 and isinstance(s[6], dict) else {}
        kind = kinds.setdefault(
            "with_prompt_rows" if args.get("prefill") else "decode_only",
            {"ms": [], "prefill": 0, "resets": 0})
        kind["ms"].append(1e3 * (s[4] - s[3]))
        kind["prefill"] += args.get("prefill", 0)
        kind["resets"] += args.get("resets", 0)
    return {
        "kinds": {k: {"steps": len(v["ms"]),
                      "step_median_ms": statistics.median(v["ms"]),
                      "prompt_rows": v["prefill"], "resets": v["resets"]}
                  for k, v in kinds.items()},
        "steps": len(turns),
        "before_first_step_ms": 1e3 * (starts[0] - t0),
        "in_step_s": sum(t["step_ms"] for t in turns) / 1e3,
        "outside_s": sum(t["outside_ms"] for t in turns) / 1e3,
        "turn_median_ms": statistics.median(t["turn_ms"] for t in turns),
        "phase_median_ms": {
            name: statistics.median(t["phases"].get(name, 0.0)
                                    for t in turns)
            for name in span_readers.PHASES},
        "longest": sorted(turns, key=lambda t: -t["turn_ms"])[:longest],
        "collections": [{"at_s": a - t0, "ms": 1e3 * d}
                        for a, d in collections
                        if d is not None and t0 <= a < t1
                        and 1e3 * d >= slow_ms]}


def _each_logits(session, precision):
    """(i, the reference's (1, T, V) logits of checked request i)."""
    import jax.numpy as jnp
    tokens, _ = session.checked
    for i in range(tokens.shape[0]):
        yield i, session.cell["reference"].decoder_logits(
            session.params, jnp.asarray(tokens[i:i + 1]), session.config,
            precision)


def control_readings(session):
    """``serve_engine.control_readings``, one request at a time: at each
    scored position, the gap in the float32 reference's logits of the
    token that a lower precision of the reference puts first."""
    import jax.numpy as jnp
    _, scored = session.checked
    out = {}
    for precision in ("fp8", "int8_weights"):
        worst = 0.0
        for (i, best), (_, low) in zip(_each_logits(session, "float32"),
                                       _each_logits(session, precision)):
            took = jnp.take_along_axis(
                best, jnp.argmax(low, axis=-1)[..., None], axis=-1)[..., 0]
            gap = jnp.max(best, axis=-1) - took
            worst = max(worst, float(jnp.max(
                jnp.where(jnp.asarray(scored[i:i + 1]), gap, 0.0))))
        out["control_" + precision] = {
            "logit_gap": worst, "bad_answers": 0.0, "missing_answers": 0.0}
    return out


def _never_carry_state(engine):
    """The planted fault: every step starts every slot's recurrent state
    from zero (the planner's ``fresh`` mask set for all slots), so that
    nothing is carried from one step to the next."""
    build = engine._build_plan

    def faulty(*args, **kw):
        plan = build(*args, **kw)
        if plan.buf is not None:
            plan.buf.fresh[:] = True
        return plan
    engine._build_plan = faulty


def fault_readings(cell, seconds):
    """What the comparison reads when the state is not carried (for the
    limits' calibration and the tests; no benchmark run calls this)."""
    session = Session(cell)
    session.fault = "state_not_carried"
    session.setup()
    session.measure(seconds)
    session.release()
    return {"fault_state_not_carried": session.check()}
