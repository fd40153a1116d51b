"""Driver ``serve_engine_motif3``: ``serve_engine`` for a configuration of
the Motif-3 family (grouped differential latent attention in sliding
layers that keep a latent ring per slot and full layers that keep latent
pages, a four-stream mHC residual, PolyNorm MLPs, one rank's share of the
routed experts and a shared expert).  The EXAONE-MoE driver's ``Session``
(its closed loop, clock, counters, turn report, window contexts and
comparison: ``logit_gap`` and ``logit_gap_p99``) with what names this
model: the config object the engine is given, and the operations a token
needs (``window["flops"]``, ``model_math_motif3``).

The planted faults of ``control_readings`` are ``reference/motif3.py``'s
``FAULTS``."""
import importlib.util
import json
import os
import sys

import model_math_motif3
import tracing

_spec = importlib.util.spec_from_file_location(
    "chipbench_drivers_serve_engine_exaone_moe",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "serve_engine_exaone_moe.py"))
exaone = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(exaone)

control_readings = exaone.control_readings


class Session(exaone.Session):
    def setup(self):
        from mxnet_tpu.models.motif3 import Motif3Config
        from mxnet_tpu.serving import ServingEngine

        # each layer's window, under the key the EXAONE session's turn
        # reads them by
        self.config = dict(self.config, sliding_windows=(
            model_math_motif3.windows(self.config)))
        c, e = self.config, self.config["engine"]
        self.cfg = Motif3Config.from_hf(c, dtype=c["dtype"])
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
        self.win_context, self.win_pumped = 0, []
        for prompt, new in self.requests.first():
            self._submit(prompt, new)
        for _ in range(self.traffic["warm_steps"]):
            self._turn(tracing.no_span)

    def measure(self, seconds, trace=False):
        """The Falcon driver's window (the turn report on standard
        error), with this family's operations."""
        deepseek = exaone.deepseek
        self.pumped, self.win_pumped = [], []
        window = deepseek.falcon.base.Session.measure(self, seconds, trace)
        window["turns"] = deepseek.falcon.turn_report(window, [])
        print("turns " + json.dumps(window["turns"]), file=sys.stderr,
              flush=True)
        # the window is the first pump (a traced slice follows it)
        rows, ctx, sampled = (b - a for a, b in zip(*self.pumped[0]))
        window["flops"] = model_math_motif3.serve_flops(
            self.config, rows, ctx, self.win_pumped[0], sampled,
            window["counters"]["moe_pairs"])
        return window
