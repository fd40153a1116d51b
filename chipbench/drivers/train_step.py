"""Driver ``train_step``: the program's jitted masked-LM training step.

System under test: ``mxnet_tpu.models.transformer.make_train_step(cfg)``,
one ``step(state, batch, rng)`` dispatch per step, each step's batch put
on the device in the window (the input path is timed).  Set-up builds ONE
object -- the compiled step with its state -- drives it from the seed
through its first steps on the window's own call and feed, reads what the
comparison needs from it (each step's loss; the first gradient, out of
Adam's first moment after one step; the parameters' change after the
last), and hands that same object to the window.
"""
import time

import numpy as np

import compare
import model_math
import tracing

CHECK_STEPS = 3


class Session:
    def __init__(self, cell):
        self.cell = cell
        self.config = cell["config"]
        self.traffic = cell["traffic"]

    # ---------------------------------------------------------- set-up --
    def setup(self):
        import jax
        from mxnet_tpu.models import transformer as T

        c, opt = self.config, self.config["optimizer"]
        ref = self.cell["reference"]
        if c["hidden_dropout_prob"] != c["attention_probs_dropout_prob"]:
            raise ValueError("the program has one dropout rate for hidden "
                             "states and attention alike")
        self.cfg = T.TransformerConfig(
            vocab_size=c["vocab_size"], max_len=c["max_position_embeddings"],
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_layers=c["num_hidden_layers"], d_ff=c["intermediate_size"],
            type_vocab_size=c["type_vocab_size"],
            dropout=c["hidden_dropout_prob"],
            dtype=c["dtype"], param_dtype=c["param_dtype"],
            use_flash=c["use_flash"], remat=c["remat"])
        init_state, self.step = T.make_train_step(
            self.cfg, learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"])
        self.batches = self.cell["generator"].generate(
            self.traffic, c, self.cell["seed"])
        self.rng = jax.random.PRNGKey(0)    # dropout is 0: never read

        # the benchmark's weights, the program's (all-zero) optimizer state
        params0 = ref.make_params(self.cell["seed"], c, c["param_dtype"])
        opt_state = jax.jit(lambda k: init_state(k)[1])(self.rng)
        self.state = (jax.tree_util.tree_map(lambda a: a.copy(), params0),
                      opt_state)
        self.n_fed = 0
        self.program = {"losses": []}
        for t in range(1, CHECK_STEPS + 1):
            loss = self._feed()
            self.program["losses"].append(float(loss))
            if t == 1:
                # Adam's first moment after one step is (1 - beta1) x the
                # gradient as the optimizer got it; a copy waits on the
                # host, so that the window's memory is the program's alone
                mu = next(s.mu for s in self.state[1] if hasattr(s, "mu"))
                self.program["grad1"] = jax.tree_util.tree_map(
                    lambda m: np.asarray(m) / (1.0 - opt["beta1"]), mu)
                self.program["grad_norms"] = np.asarray(
                    ref.leaf_norms(mu)) / (1.0 - opt["beta1"])
        self.program["change_norms"] = np.asarray(
            ref.leaf_change_norms(self.state[0], params0))
        del params0
        jax.block_until_ready(self.state)

    def _feed(self, span=tracing.no_span):
        """One step through the window's own call and feed."""
        import jax
        with span("input"):
            batch = jax.device_put(self.batches[self.n_fed
                                                % len(self.batches)])
        with span("dispatch"):
            self.state, loss = self.step(self.state, batch, self.rng)
        self.n_fed += 1
        return loss

    # ---------------------------------------------------------- window --
    def _pump(self, seconds, span=tracing.no_span):
        """Steps for ``seconds``, at most two in flight, closed by a wait
        for the last step's state.  Returns (t0, t1, losses)."""
        import jax
        losses = []
        t0 = time.perf_counter()
        while True:
            losses.append(self._feed(span))
            if len(losses) >= 2:
                with span("wait"):
                    losses[-2].block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with span("wait"):
            jax.block_until_ready(self.state)
        return t0, time.perf_counter(), losses

    def measure(self, seconds, trace=False):
        t0, t1, losses = self._pump(seconds)
        tokens_per_step = self.traffic["batch"] * self.traffic["seq_len"]
        window = {
            "t0": t0, "t1": t1, "steps": len(losses),
            "tokens": len(losses) * tokens_per_step,
            "flops": len(losses) * tokens_per_step
            * model_math.train_flops_per_token(
                self.config, self.traffic["seq_len"],
                self.cell["generator"].masked_per_row(self.traffic)
                / float(self.traffic["seq_len"])),
            "attempted": len(losses),
        }
        if trace:
            window["trace"] = tracing.traced_slice(self.cell, self.traffic,
                                                   self._pump)
        window["failed"] = int(np.sum(~np.isfinite(
            np.asarray([float(x) for x in losses]))))
        return window

    def release(self):
        self.state = None
        self.step = None

    # ----------------------------------------------------------- check --
    def check(self):
        """The first steps again, by the float32 reference from the same
        seed and the same batches, against what the program's own state
        gave."""
        c = self.config
        ref = self.cell["reference"]
        params0 = ref.make_params(self.cell["seed"], c, "float32")
        want = ref.train_reference(
            params0, self.batches[:CHECK_STEPS], c, c["optimizer"],
            row_block=self.traffic["reference_row_block"])
        return readings(self.program, want, ref)


def readings(got, want, ref):
    """The numbers compared, from the program's side ``got`` and the
    reference's ``want`` (each: losses, grad1, grad_norms, change_norms).

    The gaps of norms catch a step that does other work (half a batch, a
    state that does not move); they barely feel precision, because
    rounding noise averages out of a norm.  ``grad_diff_median`` does: the
    median leaf's norm of the DIFFERENCE between the two first gradients,
    against the reference's norm of that leaf."""
    moving = compare.moving_leaves(want["grad_norms"])
    out = {"loss_gap_step%d" % (i + 1): compare.rel_gap(g, w)
           for i, (g, w) in enumerate(zip(got["losses"], want["losses"]))}
    # one number for the steps' losses: each step alone has no fault that
    # reads ten times its sound runs, the worst of them has (PERF.md 2)
    out["loss_gap"] = max(out.values())
    out["grad_norm_gap"], _ = compare.worst_norm_gap(
        got["grad_norms"], want["grad_norms"])
    out["change_norm_gap"], _ = compare.worst_norm_gap(
        got["change_norms"], want["change_norms"], keep=moving)
    diff = np.asarray(ref.leaf_change_norms(got["grad1"], want["grad1"]),
                      np.float64)
    out["grad_diff_median"] = float(np.median(
        (diff / np.maximum(want["grad_norms"], 1e-30))[moving]))
    return out


def control_readings(session):
    """What the comparison reads with something else in the program's
    place (for calibrate.py and the tests; no benchmark run calls this):
    the reference computed in fp8, the nearest precision below the
    configuration's bfloat16; half of every batch left out, the mean taken
    over the rest; a step that returns its state unchanged."""
    c = session.config
    ref = session.cell["reference"]
    params0 = ref.make_params(session.cell["seed"], c, "float32")
    batches = session.batches[:CHECK_STEPS]
    kw = dict(row_block=session.traffic["reference_row_block"])
    want = ref.train_reference(params0, batches, c, c["optimizer"], **kw)
    half = range(session.traffic["batch"] // 2)
    return {
        "control_fp8": readings(ref.train_reference(
            params0, batches, c, c["optimizer"], precision="fp8", **kw),
            want, ref),
        "fault_half_batch": readings(ref.train_reference(
            params0, batches, c, c["optimizer"], rows=half, **kw), want, ref),
        "fault_state_unchanged": readings(ref.train_reference(
            params0, batches, c, c["optimizer"], frozen=True, **kw), want,
            ref),
    }
