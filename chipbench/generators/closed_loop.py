"""Generator ``closed_loop``: the requests of a closed loop of clients.

Every seed sends the same sizes in another order.  A block of ``block``
requests holds each of ``prompt_lengths`` equally often and the
``block`` evenly spaced quantiles of the new-token distribution (a
lognormal clamped to [min, max], after ``benchmark/traffic_trace.py``'s
``_clamped_lognormal``, taken at quantiles instead of drawn); the seed
shuffles prompts against answers within each block and draws the token
ids.  The first request of every client gets a new-token count from an
evenly spaced grid over ``first_new_tokens`` instead, so that the slots
leave step with each other before the window opens."""
import statistics

import numpy as np


def clamped_lognormal_quantiles(spec, n):
    dist = statistics.NormalDist()
    mu = float(np.log(spec["median"]))
    out = []
    for i in range(n):
        x = np.exp(mu + spec["sigma"] * dist.inv_cdf((i + 0.5) / n))
        out.append(int(min(spec["max"], max(spec["min"], round(float(x))))))
    return out


def block_sizes(traffic):
    """The fixed multiset of (prompt length, new tokens) of one block."""
    n = traffic["block"]
    lengths = traffic["prompt_lengths"]
    prompts = [lengths[i % len(lengths)] for i in range(n)]
    return prompts, clamped_lognormal_quantiles(traffic["new_tokens"], n)


class Requests:
    """``first()``: one (prompt ids, new tokens) per client.  ``next()``:
    the request a client sends when its last one has finished."""

    def __init__(self, traffic, sizes, seed):
        self.traffic = traffic
        self.vocab = sizes["vocab_size"]
        self.rng = np.random.RandomState(int(seed) % (2 ** 32))
        self.pending = []

    def _prompt(self, n):
        return self.rng.randint(1, self.vocab, n).astype(np.int32)

    def _block(self):
        prompts, new = block_sizes(self.traffic)
        prompts = self.rng.permutation(prompts)
        new = self.rng.permutation(new)
        return [(self._prompt(int(p)), int(n)) for p, n in zip(prompts, new)]

    def first(self):
        n = self.traffic["clients"]
        f = self.traffic["first_new_tokens"]
        grid = np.linspace(f["min"], f["max"], n).round().astype(int)
        new = self.rng.permutation(grid)
        block = []
        while len(block) < n:
            block += self._block()
        return [(p, int(k)) for (p, _), k in zip(block[:n], new)]

    def next(self):
        if not self.pending:
            self.pending = self._block()[::-1]
        return self.pending.pop()


def generate(traffic, sizes, seed):
    return Requests(traffic, sizes, seed)
