"""``chip_smoke.py`` off the chip: it must refuse to run, its CPU
rehearsal must pass every leg at toy size, and no ``except`` in it may
turn a failure into a ``PASS``.  What it proves ON the chip is the chip
run's to show (CHANGES.md, PR 21)."""
import ast
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, SMOKE, *args],
                          capture_output=True, text=True, timeout=600,
                          env=env)


def test_refuses_without_a_chip():
    r = _run()
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout
    assert "'cpu'" in r.stderr and "TPU" in r.stderr
    assert '"ok"' not in r.stdout and "PASS" not in r.stdout


def test_rehearsal_passes_every_leg():
    r = _run("--rehearse-cpu")
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    lines = r.stdout.splitlines()
    assert lines[0] == "REHEARSAL — not a chip run"
    for leg in ("train_resnet50", "serve_full", "kernels", "multichip"):
        assert any(l.startswith("PASS " + leg) for l in lines), r.stdout
    assert "FAIL" not in r.stdout
    # a rehearsal is not a result: no JSON line a driver could mistake
    assert '"ok"' not in r.stdout
    assert lines[-1].startswith("REHEARSAL — not a chip run")


def test_no_except_leads_to_a_pass():
    """Every handler in the script either re-raises or records the leg
    as failed (``ok = False``)."""
    with open(SMOKE) as f:
        tree = ast.parse(f.read())
    handlers = [n for n in ast.walk(tree)
                if isinstance(n, ast.ExceptHandler)]
    assert handlers, "the leg runner's handler is gone?"
    for h in handlers:
        fails = any(
            isinstance(s, ast.Raise) or (
                isinstance(s, ast.Assign)
                and [getattr(t, "id", None) for t in s.targets] == ["ok"]
                and isinstance(s.value, ast.Constant)
                and s.value.value is False)
            for s in h.body)
        assert fails, "except at line %d does not fail the leg" % h.lineno
