"""Generators: the same seed gives the same inputs, every seed the same
sizes, everything inside its stated range; large seeds work."""
import collections

import numpy as np

import run

BIG = 2 ** 31 + 12345
load = run.load_module


def data(kind, name):
    return run.read_json(run.HERE, kind, name + ".json")


def test_mlm_batches():
    gen = load("generators", "mlm_batches")
    traffic = data("traffic", "mlm_b16_s512")
    sizes = data("configs", "bert_base")
    a = gen.generate(traffic, sizes, BIG)
    b = gen.generate(traffic, sizes, BIG)
    c = gen.generate(traffic, sizes, 7)
    assert len(a) == 8
    for x, y in zip(a, b):
        for k in x:
            assert np.array_equal(x[k], y[k])
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])
    for batch in a + c:
        assert batch["tokens"].shape == (16, 512)
        assert batch["tokens"].dtype == np.int32
        masked = batch["labels"] >= 0
        assert (masked.sum(axis=1) == 77).all()     # round(0.15 * 512)
        assert (batch["tokens"][masked] == 103).all()
        assert (batch["labels"][~masked] == -100).all()
        assert batch["tokens"].min() >= 1
        assert batch["tokens"].max() < 30522
        assert set(np.unique(batch["type_ids"])) == {0, 1}
        # rows all differ
        assert len({row.tobytes() for row in batch["tokens"]}) == 16


def sizes_of(reqs):
    return collections.Counter((p.size, n) for p, n in reqs)


def test_closed_loop():
    gen = load("generators", "closed_loop")
    traffic = data("traffic", "closed96_p32-96_o128-400")
    sizes = data("configs", "bert_large_decoder")
    a, b, c = (gen.generate(traffic, sizes, s) for s in (BIG, BIG, 7))
    fa, fb, fc = a.first(), b.first(), c.first()
    assert len(fa) == 96
    for (p, n), (q, m) in zip(fa, fb):
        assert np.array_equal(p, q) and n == m
    # every seed: the same first-round answers, in another order
    assert sorted(n for _, n in fa) == sorted(n for _, n in fc)
    assert [n for _, n in fa] != [n for _, n in fc]
    assert min(n for _, n in fa) == 16 and max(n for _, n in fa) == 400
    na = [a.next() for _ in range(360)]
    nb = [b.next() for _ in range(360)]
    nc = [c.next() for _ in range(360)]
    for (p, n), (q, m) in zip(na, nb):
        assert np.array_equal(p, q) and n == m
    # whole blocks hold the same multiset of prompt lengths and of answers
    assert collections.Counter(p.size for p, _ in na) == \
        collections.Counter(p.size for p, _ in nc)
    assert sorted(n for _, n in na) == sorted(n for _, n in nc)
    assert sizes_of(na) != sizes_of(nc)
    for p, n in na + fa:
        assert p.size in (32, 48, 64, 80, 96)
        assert p.dtype == np.int32 and p.min() >= 1 and p.max() < 30522
        assert p.size + n <= 512
    for _, n in na:
        assert 128 <= n <= 400
    block = [n for _, n in na[:120]]
    assert 250 <= np.median(block) <= 262
    assert collections.Counter(p.size for p, _ in na[:120]) == \
        {32: 24, 48: 24, 64: 24, 80: 24, 96: 24}


def test_quantiles_clamped():
    gen = load("generators", "closed_loop")
    q = gen.clamped_lognormal_quantiles(
        {"median": 256, "sigma": 0.35, "min": 128, "max": 400}, 120)
    assert q == sorted(q) and q[0] >= 128 and q[-1] <= 400
    assert q.count(400) >= 1        # the upper tail is clamped
