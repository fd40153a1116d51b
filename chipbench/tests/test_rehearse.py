"""One CPU ``--rehearse`` run of each cell at toy size; the control, which
has to come out as not correct; and the rest of a run driven with the timed
path broken underneath, which has to come out as not correct too.

All of it runs the harness's own ``main`` (the look for a chip is what
``--rehearse`` skips) against the limits in ``limits/``: the same numbers
the chip runs are held to."""
import json

import pytest

import compare
import run

CELLS = [w["name"] for w in run.read_json(run.ROOT, "BENCHMARK.json")
         ["workloads"]]
TRAIN = "bert_base.pretrain_s512"
SERVE = "bert_large_decoder.decode_heavy"
SEED = 2 ** 31 + 77


def drive(capsys, cell, trace=0, seed=SEED, seconds=1.0):
    capsys.readouterr()
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    # the numbers compared, beside their limits: the last lines of stderr
    # and the last key of the result
    assert list(line)[-1] == "compared"
    assert err.strip().splitlines()[-1].endswith("correct = %s"
                                                 % line["correct"])
    return line


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearse_run_is_correct(capsys, cell, trace):
    line = drive(capsys, cell, trace)
    assert line["correct"] is True, line["compared"]
    assert line["rehearse"] is True and line["metrics"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert set(line["compared"]) == set(compare.load_limits(cell, True))


def test_no_chip_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def session_for(cell_name, seed=SEED):
    cell = run.load_cell(cell_name, seed, True)
    driver = run.prepare(cell)
    return driver, driver.Session(cell)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The reference in the program's place, computed in fp8 (the nearest
    precision below the configuration's bfloat16), fails the cell's limits;
    so does each planted fault the driver knows."""
    driver, session = session_for(cell)
    session.setup()
    session.measure(1.0)
    session.release()
    limits = compare.load_limits(cell, True)
    ok, compared = compare.judge(session.check(), limits)
    assert ok, compared
    controls = driver.control_readings(session)
    assert "control_fp8" in controls
    for name, readings in controls.items():
        if name == "control_int8_weights":
            continue        # read for information: PERF.md section 2
        ok, compared = compare.judge(readings, limits)
        assert not ok, (name, compared)


# ---- the timed path broken underneath ----------------------------------

def broken_train_step(monkeypatch, how):
    from mxnet_tpu.models import transformer as T
    real = T.make_train_step

    def make(cfg, **kw):
        init_state, step = real(cfg, **kw)

        def unchanged(state, batch, rng):
            _, loss = step(jax_copy(state), batch, rng)
            return state, loss

        def half_batch(state, batch, rng):
            half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half, rng)

        return init_state, {"state_unchanged": unchanged,
                            "half_batch": half_batch}[how]

    monkeypatch.setattr(T, "make_train_step", make)


def jax_copy(tree):
    import jax
    return jax.tree_util.tree_map(lambda a: a.copy(), tree)


@pytest.mark.parametrize("how", ["state_unchanged", "half_batch"])
def test_broken_training_step_is_not_correct(capsys, monkeypatch, how):
    broken_train_step(monkeypatch, how)
    line = drive(capsys, TRAIN)
    assert line["correct"] is False, line["compared"]


def test_altered_token_is_not_correct(capsys, monkeypatch):
    """A token altered where it is produced: one step in eight, every
    slot's argmax comes back off by one."""
    from mxnet_tpu.serving import engine as E
    real = E._make_step

    def make(cfg, *a, **kw):
        fn = real(cfg, *a, **kw)
        calls = [0]

        def step(params, pools, *rest):
            tok, pools = fn(params, pools, *rest)
            calls[0] += 1
            if calls[0] % 8 == 0:
                tok = (tok + 1) % cfg.vocab_size
            return tok, pools
        return step

    monkeypatch.setattr(E, "_make_step", make)
    line = drive(capsys, SERVE, seconds=2.0)
    assert line["correct"] is False, line["compared"]
    assert line["compared"]["logit_gap"]["value"] > \
        line["compared"]["logit_gap"]["limit"]
