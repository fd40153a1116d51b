"""Driver ``serve_engine``: the program's paged serving engine under a
closed loop of clients.

System under test: ``mxnet_tpu.serving.ServingEngine`` through
``submit()`` and ``step()`` -- chunked prefill and decode rows mixed in one
step, K/V written into pages, attention through the block table, the LM
head, greedy argmax, slots reused.  One thread drives it: ``step()``, then
the clients' side (stamp the tokens that arrived, send the next request of
every client whose last one finished).

The clock is the benchmark's: a request's submit time is taken just
before ``submit()``, a token's arrival when the ``step()`` that committed
it has returned (the engine's one host read-back a step has then
happened).  From the program it reads only how many tokens each request
holds, the tokens themselves, and the ``stats`` counters.
"""
import time

import numpy as np

import model_math
import tracing


class Client:
    """One request as the client sees it."""
    __slots__ = ("rid", "prompt", "new", "submit_t", "stamps", "done_t")

    def __init__(self, rid, prompt, new, submit_t):
        self.rid, self.prompt, self.new = rid, prompt, new
        self.submit_t, self.stamps, self.done_t = submit_t, [], None


class Session:
    def __init__(self, cell):
        self.cell = cell
        self.config = cell["config"]
        self.traffic = cell["traffic"]

    # ---------------------------------------------------------- set-up --
    def setup(self):
        from mxnet_tpu.models import gpt
        from mxnet_tpu.serving import ServingEngine

        c, e = self.config, self.config["engine"]
        self.cfg = gpt.gpt_config(
            vocab_size=c["vocab_size"], max_len=c["max_position_embeddings"],
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_layers=c["num_hidden_layers"], d_ff=c["intermediate_size"],
            dropout=0.0, dtype=c["dtype"], param_dtype=c["param_dtype"],
            use_flash=False, remat=False)
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
        self.active = {}          # rid -> Client, in flight
        self.finished = []        # Clients, in order of finish
        self.submitted = []       # Clients, in order of submit
        self.step_ms = []
        self.work = [0, 0, 0]     # rows, summed contexts, sampled rows
        for prompt, new in self.requests.first():
            self._submit(prompt, new)
        # the loop runs from before the window: the first step compiles,
        # the rest let the slots leave step with each other
        for _ in range(self.traffic["warm_steps"]):
            self._turn(tracing.no_span)

    def _submit(self, prompt, new):
        t = time.perf_counter()
        rid = self.engine.submit(prompt, new)
        client = Client(rid, prompt, new, t)
        self.active[rid] = client
        self.submitted.append(client)

    def _turn(self, span):
        """One engine step, then the clients' side of it."""
        t0 = time.perf_counter()
        with span("step"):
            self.engine.step()
        now = time.perf_counter()
        self.step_ms.append((now - t0) * 1e3)
        with span("client"):
            reqs = self.engine.requests
            done = []
            for rid, client in self.active.items():
                n = len(reqs[rid].generated)
                k = len(client.stamps)
                if n == k:
                    continue
                P = client.prompt.size
                if k == 0:      # the prompt's rows, counted at first token
                    self.work[0] += P - 1
                    self.work[1] += P * (P - 1) // 2
                for i in range(k, n):   # token i + 1 came from position
                    client.stamps.append(now)       # P + i - 1
                    self.work[0] += 1
                    self.work[1] += P + i
                    self.work[2] += 1
                if n >= client.new:
                    done.append(rid)
            for rid in done:
                client = self.active.pop(rid)
                client.done_t = now
                self.finished.append(client)
                self._submit(*self.requests.next())
        return now

    # ---------------------------------------------------------- window --
    def _pump(self, seconds, span=tracing.no_span):
        t0 = time.perf_counter()
        while self._turn(span) - t0 < seconds:
            pass
        return t0, time.perf_counter()

    def measure(self, seconds, trace=False):
        stats0 = dict(self.engine.stats)
        work0 = list(self.work)
        s0 = len(self.step_ms)
        t0, t1 = self._pump(seconds)
        stats1 = dict(self.engine.stats)
        work1 = list(self.work)
        s1 = len(self.step_ms)
        window = {"t0": t0, "t1": t1}
        if trace:
            window["trace"] = tracing.traced_slice(self.cell, self.traffic,
                                                   self._pump)
        # a tail is the tail of ALL requests sent in the window: wait, the
        # loop going on as before, until each has its first token
        mine = [c for c in self.submitted if t0 <= c.submit_t < t1]
        deadline = time.perf_counter() + 60.0
        while any(not c.stamps for c in mine) \
                and time.perf_counter() < deadline:
            self._turn(tracing.no_span)

        every = self.submitted
        stamps = [np.asarray(c.stamps) for c in every if c.stamps]
        gaps = [np.diff(s)[(s[1:] >= t0) & (s[1:] < t1)] for s in stamps]
        rows, ctx, sampled = (b - a for a, b in zip(work0, work1))
        window.update({
            "tokens": int(sum(((s >= t0) & (s < t1)).sum() for s in stamps)),
            "ttft_ms": [1e3 * (c.stamps[0] - c.submit_t)
                        for c in mine if c.stamps],
            "itl_ms": 1e3 * np.concatenate(gaps) if gaps else np.zeros(0),
            "engine_step_ms": self.step_ms[s0:s1],
            "flops": model_math.serve_flops(self.config, rows, ctx, sampled),
            "counters": {k: stats1[k] - stats0[k] for k in stats1},
            "attempted": len(mine),
            "failed": sum(not c.stamps for c in mine),
        })
        self.window = (t0, t1)
        return window

    def release(self):
        """Free the program's state; keep what it served."""
        reqs = self.engine.requests
        t0, t1 = self.window
        self.served = [
            (c, np.asarray(reqs[c.rid].generated, np.int32),
             np.asarray(reqs[c.rid].prompt, np.int32))
            for c in self.finished if t0 <= c.done_t < t1]
        self.engine.close()
        self.engine.cache.pools = None
        self.engine = None

    # ----------------------------------------------------------- check --
    def check(self):
        """Every request finished in the window: as many tokens as asked
        for, the prompt it was sent, ids inside the vocabulary.  A sample
        of them drawn from the seed, the longest among it: the float32
        reference once over prompt + served tokens, and the widest gap by
        which a served token's logit lies below the reference's best."""
        import jax.numpy as jnp
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
        sample = pick_sample(self.served, self.traffic["check_requests"],
                             self.cell["seed"])
        T = c["max_position_embeddings"]
        tokens = np.zeros((len(sample), T), np.int32)
        scored = np.zeros((len(sample), T), bool)
        for i, (client, new, prompt) in enumerate(sample):
            seq = np.concatenate([prompt, new])[:T]
            tokens[i, :seq.size] = seq
            scored[i, prompt.size - 1:seq.size - 1] = True
        logits = self.cell["reference"].decoder_logits(self.params, tokens, c)
        out["logit_gap"] = float(worst_gap(logits, jnp.asarray(tokens),
                                           jnp.asarray(scored)))
        self.checked = (tokens, scored)
        return out


def pick_sample(served, n, seed):
    """``n`` finished requests drawn from the seed, the longest in it."""
    order = np.random.RandomState(int(seed) % (2 ** 32)).permutation(
        len(served))
    longest = max(range(len(served)),
                  key=lambda i: served[i][1].size + served[i][2].size)
    picks = [longest] + [int(i) for i in order if i != longest][:n - 1]
    return [served[i] for i in picks]


def worst_gap(logits, tokens, scored):
    """Row t of ``logits`` scores the token at t + 1: the widest gap, over
    the scored rows, between the row's best logit and the logit of the
    token that was served."""
    import jax.numpy as jnp
    nxt = jnp.roll(tokens, -1, axis=1)
    took = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
    gap = jnp.max(logits, axis=-1) - took
    return jnp.max(jnp.where(scored, gap, 0.0))


def control_readings(session):
    """What the comparison reads with the reference, computed in a lower
    precision, in the program's place (for calibrate.py and the tests; no
    benchmark run calls this).  It need not decode: at each scored position
    of the same prompts and tokens, the gap of the token that the lower
    precision puts first."""
    import jax.numpy as jnp
    c = session.config
    ref = session.cell["reference"]
    tokens, scored = session.checked
    best = ref.decoder_logits(session.params, tokens, c)
    out = {}
    for precision in ("fp8", "int8_weights"):
        low = ref.decoder_logits(session.params, tokens, c, precision)
        first = jnp.argmax(low, axis=-1)
        took = jnp.take_along_axis(best, first[..., None], axis=-1)[..., 0]
        gap = jnp.max(best, axis=-1) - took
        out["control_" + precision] = {
            "logit_gap": float(jnp.max(jnp.where(jnp.asarray(scored), gap,
                                                 0.0))),
            "bad_answers": 0.0, "missing_answers": 0.0}
    return out
