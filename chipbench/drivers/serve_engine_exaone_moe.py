"""Driver ``serve_engine_exaone_moe``: ``serve_engine`` for a configuration
of the EXAONE-MoE family (sliding-window attention layers that keep a
ring per slot beside full attention layers that keep pages; one rank's
share of the routed experts and a shared expert).  The same closed loop,
clock, counters, turn report, comparison (``logit_gap`` and
``logit_gap_p99``: routing is discrete) and controls as the DeepSeek-V3
driver, whose ``Session`` (the Falcon-H1 driver's, extended) it extends;
what differs is what names the model: the config object the engine is
given, and the operations a token needs (``window["flops"]``): a full
layer's attention reads a row's whole context, a sliding layer's at most
the window (the contexts are summed both ways as the clients' side books
them), and the routed part follows the pairs the engine COUNTED
(``moe_pairs``).

The planted faults of ``control_readings`` are
``reference/exaone_moe.py``'s ``FAULTS``."""
import importlib.util
import json
import os
import sys

import model_math_exaone_moe
import tracing

_spec = importlib.util.spec_from_file_location(
    "chipbench_drivers_serve_engine_deepseek_v3",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "serve_engine_deepseek_v3.py"))
deepseek = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(deepseek)

control_readings = deepseek.control_readings


class Session(deepseek.Session):
    def setup(self):
        from mxnet_tpu.models.exaone_moe import ExaoneMoeConfig
        from mxnet_tpu.serving import ServingEngine

        c, e = self.config, self.config["engine"]
        self.cfg = ExaoneMoeConfig.from_hf(c, dtype=c["dtype"])
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
        # the contexts of the same rows, each capped at the window
        self.win_context, self.win_pumped = 0, []
        for prompt, new in self.requests.first():
            self._submit(prompt, new)
        for _ in range(self.traffic["warm_steps"]):
            self._turn(tracing.no_span)

    def _turn(self, span):
        """The base turn; beside its contexts, the same rows' contexts
        capped at the window (a sliding layer's reads)."""
        w = model_math_exaone_moe.window(self.config)
        seen = [(c, len(c.stamps)) for c in self.active.values()]
        now = super()._turn(span)
        for client, k in seen:
            P = client.prompt.size
            if k == 0 and client.stamps:
                self.win_context += \
                    model_math_exaone_moe.windowed_prompt_context(P, w)
            self.win_context += sum(min(P + i, w)
                                    for i in range(k, len(client.stamps)))
        return now

    def _pump(self, seconds, span=tracing.no_span):
        before = self.win_context
        out = super()._pump(seconds, span)
        self.win_pumped.append(self.win_context - before)
        return out

    def measure(self, seconds, trace=False):
        """The Falcon driver's window (the turn report on standard
        error), with this family's operations."""
        self.pumped, self.win_pumped = [], []
        window = deepseek.falcon.base.Session.measure(self, seconds, trace)
        window["turns"] = deepseek.falcon.turn_report(window, [])
        print("turns " + json.dumps(window["turns"]), file=sys.stderr,
              flush=True)
        # the window is the first pump (a traced slice follows it)
        rows, ctx, sampled = (b - a for a, b in zip(*self.pumped[0]))
        window["flops"] = model_math_exaone_moe.serve_flops(
            self.config, rows, ctx, self.win_pumped[0], sampled,
            window["counters"]["moe_pairs"])
        return window
