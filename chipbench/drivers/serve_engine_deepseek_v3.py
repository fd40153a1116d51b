"""Driver ``serve_engine_deepseek_v3``: ``serve_engine`` for a
configuration of the DeepSeek-V3 family (latent attention, routed
experts of which this chip holds a share).  The same closed loop, clock,
counters, turn report and comparison as the Falcon-H1 driver, whose
``Session`` it extends; what differs is what names the model: the config
object the engine is given, and the operations a token needs
(``window["flops"]``), whose routed part follows the pairs the engine
COUNTED on held experts (``moe_pairs``), not an expectation.

The comparison's controls (``control_readings``, for ``calibrate.py``
and the tests; no benchmark run calls them) are the reference in a lower
precision and the reference with ONE planted departure from the
published layer (``reference/deepseek_v3.py`` ``FAULTS``) in the
program's place: at each scored position of the same prompts and tokens,
the gap, in the float32 reference's logits, of the token the other puts
first."""
import importlib.util
import json
import os
import sys

import numpy as np

import model_math_deepseek_v3
import tracing

_spec = importlib.util.spec_from_file_location(
    "chipbench_drivers_serve_engine_falcon_h1",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "serve_engine_falcon_h1.py"))
falcon = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(falcon)


class Session(falcon.Session):
    def setup(self):
        from mxnet_tpu.models.deepseek_v3 import DeepseekV3Config
        from mxnet_tpu.serving import ServingEngine

        c, e = self.config, self.config["engine"]
        self.cfg = DeepseekV3Config.from_hf(c, dtype=c["dtype"])
        self.params = self.cell["reference"].make_params(
            self.cell["seed"], c, c["param_dtype"])
        self.engine = ServingEngine(
            self.params, self.cfg, num_slots=e["num_slots"],
            page_size=e["page_size"], pages_per_slot=e["pages_per_slot"],
            prefill_chunk=e["prefill_chunk"], kv_int8=e["kv_int8"],
            prefix_cache=e["prefix_cache"], metrics=False,
            device=self.cell["devices"][0])
        self.requests = self.cell["generator"].generate(
            self.traffic, c, self.cell["seed"])
        self.active, self.finished, self.submitted = {}, [], []
        self.step_ms, self.work, self.pumped = [], [0, 0, 0], []
        for prompt, new in self.requests.first():
            self._submit(prompt, new)
        for _ in range(self.traffic["warm_steps"]):
            self._turn(tracing.no_span)

    def measure(self, seconds, trace=False):
        """The Falcon driver's window (the turn report on standard
        error), with this family's operations."""
        self.pumped = []
        window = falcon.base.Session.measure(self, seconds, trace)
        window["turns"] = falcon.turn_report(window, [])
        print("turns " + json.dumps(window["turns"]), file=sys.stderr,
              flush=True)
        # the window is the first pump (a traced slice follows it)
        rows, ctx, sampled = (b - a for a, b in zip(*self.pumped[0]))
        window["flops"] = model_math_deepseek_v3.serve_flops(
            self.config, rows, ctx, sampled,
            window["counters"]["moe_pairs"])
        return window


    def check(self):
        """The Falcon driver's comparison, one request at a time, with
        two numbers read from the gaps.  ``logit_gap``: the widest gap by
        which a served token's float32-reference logit lies below the
        reference's best, as in the other serving cells.
        ``logit_gap_p99``: the 99th percentile of that gap over all
        scored positions of the sample.  Routing is discrete: where the
        bfloat16 program and the float32 reference break a near-tie
        between two experts differently, a whole expert's term is
        swapped at that row, and a few rows in a thousand read a gap of
        1–2 that says nothing of precision; the percentile looks under
        those rows, where a lower precision or a changed layer moves
        every row.  Each request goes into an array as long as a slot's
        pool whatever the sample's longest (the zeros after its end
        touch no earlier row): one compiled reference for every run."""
        e = self.config["engine"]
        V = self.config["vocab_size"]
        bad = 0
        for client, new, prompt in self.served:
            bad += int(new.size != client.new
                       or not np.array_equal(prompt, client.prompt)
                       or new.min() < 0 or new.max() >= V)
        out = {"bad_answers": float(bad),
               "missing_answers": float(len(self.served) == 0)}
        if not self.served:
            return out
        sample = falcon.base.pick_sample(
            self.served, self.traffic["check_requests"], self.cell["seed"])
        T = e["page_size"] * e["pages_per_slot"]
        tokens = np.zeros((len(sample), T), np.int32)
        scored = np.zeros((len(sample), T), bool)
        for i, (client, new, prompt) in enumerate(sample):
            seq = np.concatenate([prompt, new])
            tokens[i, :seq.size] = seq
            scored[i, prompt.size - 1:seq.size - 1] = True
        self.checked = (tokens, scored)
        out.update(_read(np.concatenate([
            _gaps(logits, np.roll(tokens[i], -1)[None], scored[i:i + 1])
            for i, logits in falcon._each_logits(self, "float32")])))
        return out


def _gaps(best, chosen, scored):
    """At the scored positions of one request: the gap between the
    reference's best logit and its logit of the token ``chosen`` there
    ((1, T) ids against (1, T, V) logits)."""
    import jax.numpy as jnp
    took = jnp.take_along_axis(best, jnp.asarray(chosen)[..., None],
                               axis=-1)[..., 0]
    return np.asarray(jnp.max(best, axis=-1) - took)[scored]


def _read(gaps):
    return {"logit_gap": float(gaps.max()),
            "logit_gap_p99": float(np.percentile(gaps, 99))}


def control_readings(session):
    """{name: readings} for the lower precision and each planted
    fault, one checked request at a time: the reference computed the
    other way chooses the tokens."""
    import jax.numpy as jnp
    ref = session.cell["reference"]
    tokens, scored = session.checked
    others = [("control_fp8", {"precision": "fp8"})] \
        + [("fault_" + f, {"fault": f}) for f in ref.FAULTS]
    gaps = {name: [] for name, _ in others}
    for i in range(tokens.shape[0]):
        seq = jnp.asarray(tokens[i:i + 1])
        best = ref.decoder_logits(session.params, seq, session.config)
        for name, how in others:
            low = ref.decoder_logits(session.params, seq, session.config,
                                     **how)
            gaps[name].append(_gaps(best, jnp.argmax(low, axis=-1),
                                    scored[i:i + 1]))
    return {name: dict(_read(np.concatenate(g)), bad_answers=0.0,
                       missing_answers=0.0) for name, g in gaps.items()}
