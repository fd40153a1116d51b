"""Tier-1 gate for the mxlint static-analysis suite (ISSUE 4/7/8).

Three layers of assertion:

1. **Live repo is clean** — every analyzer runs over the working tree
   and reports ZERO new violations (pragma- and baseline-filtered).
   This is the gate that keeps ABI drift, hot-loop host syncs,
   locking-discipline regressions, dropped step-program donation, and
   HBM-footprint creep out of future PRs.
2. **Rules actually fire** — seeded-violation fixtures under
   ``tests/fixtures/mxlint/`` prove each rule detects its target
   exactly as often as seeded, and that the pragma / requires() /
   baseline suppression paths work.
3. **Coverage invariants** — every ``MX*`` function in ``c_api.h`` has
   an explicit argtypes/restype entry (zero baselined ABI findings —
   acceptance criterion), graphlint's budget manifest and sharding
   audit stay current, and the runner end-to-end stays under the
   tier-1 time budget (parsing + abstract tracing only: no native
   build, no compilation, no program execution).
"""
import collections
import importlib.util
import json
import os
import time

import pytest

from tools.analysis import (abi, asynclint, envlint, graphlint,
                            jaxlint, native_lint, protolint,
                            pylocklint)
from tools.analysis.findings import (Finding, apply_pragmas,
                                     load_baseline, split_new)
from tools.analysis.runner import (BINDINGS, HEADER, REPO_ROOT,
                                   changed_files, findings_json,
                                   run_all)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "mxlint")


def _rules(findings):
    return collections.Counter(f.rule for f in findings)


def _load_graph_fixture():
    path = os.path.join(FIXTURES, "graph_fixture.py")
    spec = importlib.util.spec_from_file_location("graph_fixture", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# 1. live repo
# ---------------------------------------------------------------------------
class TestLiveRepo:
    def test_runner_clean_and_fast(self):
        t0 = time.perf_counter()
        report = run_all()
        dt = time.perf_counter() - t0
        assert report["new"] == [], \
            "new static-analysis violations:\n" + "\n".join(
                "  %s" % f for f in report["new"])
        assert dt < 20.0, "analyzers must stay tier-1 cheap (%.1fs)" % dt

    def test_abi_zero_findings_even_baselined(self):
        """Acceptance criterion: zero *baselined* ABI findings — the
        argtypes table is complete and exact, not grandfathered."""
        fs = abi.check(os.path.join(REPO_ROOT, HEADER),
                       os.path.join(REPO_ROOT, BINDINGS),
                       HEADER, BINDINGS)
        assert fs == [], "\n".join(str(f) for f in fs)

    def test_abi_header_fully_covered(self):
        """Every header function bound; every binding in the header."""
        header = abi.parse_header(os.path.join(REPO_ROOT, HEADER))
        protos = abi.load_prototypes(os.path.join(REPO_ROOT, BINDINGS))
        assert set(header) == set(protos)
        # the header is the real one, not a stub
        assert len(header) >= 40
        for name in ("MXEnginePushAsync", "MXImageRecordLoaderCreateEx",
                     "MXShmData", "MXEngineStats"):
            assert name in header

    def test_prototypes_match_loaded_library(self):
        """The table applies cleanly to the shipped binary: every entry
        resolves to an exported symbol (catches header/table symbols
        the .so does not actually export)."""
        from mxnet_tpu import native
        if not native.available():
            pytest.skip("native library unavailable")
        missing = native._apply_prototypes(native.lib())
        assert missing == []

    def test_pylocklint_zero_findings_even_baselined(self):
        """ISSUE 7 acceptance criterion: pylocklint reports ZERO
        findings with an EMPTY baseline over serving/, obs/, io/ —
        nothing grandfathered."""
        fs = pylocklint.run(REPO_ROOT)
        assert fs == [], "\n".join(str(f) for f in fs)

    def test_pylocklint_guards_the_admit_ref_leak_fix(self):
        """Deleting the round-12 try/except in ServingEngine._admit
        reintroduces the py-ref-leak finding — the pass genuinely
        guards the fix shipped in this PR (PR-4 pattern)."""
        path = os.path.join(REPO_ROOT, "mxnet_tpu/serving/engine.py")
        src = open(path).read()
        guarded = ("            except BaseException:\n")
        assert guarded in src
        # strip the handler body's release (keep it syntactically
        # valid: the handler just re-raises)
        broken = src.replace(
            "                if entries:\n"
            "                    self.prefix.release(entries)\n"
            "                raise\n",
            "                raise\n", 1)
        assert broken != src
        fs = pylocklint.lint_source(broken,
                                    "mxnet_tpu/serving/engine.py")
        assert collections.Counter(
            f.rule for f in fs)["py-ref-leak"] >= 1

    def test_changed_only_scopes_the_run(self):
        """--changed-only reports only changed files (the full parse
        still happens, so this is a reporting scope, not a soundness
        hole in tier-1 — which always runs full)."""
        cf = changed_files(REPO_ROOT)
        if cf is None:
            pytest.skip("git unavailable")
        report = run_all(changed_only=True)
        assert report["changed"] is not None
        allowed = set(report["changed"])
        for f in report["findings"]:
            assert f.path in allowed or f.path in (HEADER, BINDINGS)

    def test_known_intentional_sync_is_pragmad(self):
        """The serving engine's intended device sync stays auditable.
        Round 21 split the step into dispatch + drain; since PR 30
        there is one step loop and so ONE pragma'd readback site,
        ``_drain``'s ``np.asarray`` of the step result it receives as
        a parameter — and the linter honors it (stripping the pragma
        makes the finding reappear)."""
        path = os.path.join(REPO_ROOT, "mxnet_tpu/serving/engine.py")
        src = open(path).read()
        assert src.count("mxlint: allow(host-sync)") == 1
        stripped = src.replace("# mxlint: allow(host-sync)", "#")
        fs = jaxlint.lint_source(stripped, "mxnet_tpu/serving/engine.py")
        assert _rules(fs)["host-sync"] == 1


# ---------------------------------------------------------------------------
# 2. seeded fixtures — each rule fires, suppression works
# ---------------------------------------------------------------------------
class TestAbiFixtures:
    @pytest.fixture(scope="class")
    def findings(self):
        return abi.check(os.path.join(FIXTURES, "abi_fixture.h"),
                         os.path.join(FIXTURES,
                                      "abi_fixture_bindings.py"),
                         "abi_fixture.h", "abi_fixture_bindings.py")

    def test_each_rule_fires_exactly_once(self, findings):
        assert _rules(findings) == {
            "abi-argtypes": 1,      # MXFixDrift: POINTER(c_int)
            "abi-restype": 1,       # MXFixRet: c_int vs const char*
            "abi-argcount": 1,      # MXFixCount: 1 vs 2
            "abi-unbound": 1,       # MXFixUnbound
            "abi-missing-argtypes": 1,   # MXFixUnbound call site
            "abi-unknown-symbol": 2,     # MXFixPhantom + MXFixNowhere
        }

    def test_drift_details(self, findings):
        by_sym = {(f.rule, f.symbol) for f in findings}
        assert ("abi-argtypes", "MXFixDrift") in by_sym
        assert ("abi-restype", "MXFixRet") in by_sym
        assert ("abi-unbound", "MXFixUnbound") in by_sym

    def test_baseline_suppresses(self, findings):
        baseline = {f.key for f in findings if f.rule == "abi-argtypes"}
        new, old = split_new(findings, baseline)
        assert _rules(old) == {"abi-argtypes": 1}
        assert "abi-argtypes" not in _rules(new)

    def test_good_binding_clean(self):
        header = abi.parse_header(os.path.join(FIXTURES,
                                               "abi_fixture.h"))
        assert header["MXFixGood"] == ("int",
                                       ["const char*", "uint64_t*"])


class TestJaxFixtures:
    @pytest.fixture(scope="class")
    def findings(self):
        src = open(os.path.join(FIXTURES, "jax_fixture.py")).read()
        return jaxlint.lint_source(src, "jax_fixture.py",
                                   region_re=".*", clock=True)

    def test_counts(self, findings):
        assert _rules(findings) == {"host-sync": 2, "retrace": 2,
                                    "clock-mix": 1}

    def test_pragma_suppressed_twins(self, findings):
        # each rule seeded one extra pragma'd violation — none surface
        lines = {(f.rule, f.line) for f in findings}
        src = open(os.path.join(FIXTURES, "jax_fixture.py")).read()
        for i, text in enumerate(src.splitlines(), 1):
            if "suppressed twin" in text:
                assert not any(ln in (i, i + 1) for _, ln in lines)

    def test_jnp_asarray_rebind_keeps_taint(self):
        """jnp.asarray is host->device — rebinding through it must NOT
        launder the taint (code-review regression): the later float()
        is still a real device sync and must flag."""
        src = ("import jax.numpy as jnp\n"
               "def step(self, x):\n"
               "    out = self._step_fn(x)\n"
               "    y = jnp.asarray(out)\n"
               "    return float(y)\n")
        fs = jaxlint.lint_source(src, "m.py", region_re=".*",
                                 clock=False)
        assert _rules(fs) == {"host-sync": 1}
        # while a genuine host materialization DOES clear it
        src_np = src.replace("jnp.asarray", "np.asarray")
        fs_np = jaxlint.lint_source(src_np, "m.py", region_re=".*",
                                    clock=False)
        assert _rules(fs_np) == {"host-sync": 1}  # the np.asarray line
        assert fs_np[0].line == 4

    def test_taint_not_overbroad(self, findings):
        # np.asarray of an untainted arg and perf_counter never flag
        msgs = [f for f in findings if f.line == 0]
        assert msgs == []
        src_lines = open(os.path.join(FIXTURES,
                                      "jax_fixture.py")).read().splitlines()
        for f in findings:
            assert "must NOT fire" not in src_lines[f.line - 1]


class TestNativeFixtures:
    CFG = {
        "order": {"alpha_mu_": 0, "beta_mu_": 1},
        "guarded": {"member": {"count": "alpha_mu_"},
                    "self": {"shared_": "alpha_mu_"}},
        "cv_preds": {"quit_": "beta_mu_"},
    }

    @pytest.fixture(scope="class")
    def findings(self):
        return native_lint.lint_file(
            os.path.join(FIXTURES, "native_fixture.cc"),
            "native_fixture.cc", config=self.CFG)

    def test_counts(self, findings):
        assert _rules(findings) == {
            "lock-order": 2,          # direct + transitive
            "guarded-field": 2,       # box->count + shared_ (one
                                      # pragma'd twin suppressed)
            "cv-wait-predicate": 1,
            "cv-pred-unlocked": 1,
        }

    def test_direct_and_transitive_lock_order(self, findings):
        msgs = [f.message for f in findings if f.rule == "lock-order"]
        assert any("holding beta_mu_" in m for m in msgs)
        assert any("call to AlphaOnly()" in m for m in msgs)

    def test_requires_annotation_honored(self, findings):
        # GuardedPrecondition's body would fire without requires()
        src = open(os.path.join(FIXTURES, "native_fixture.cc")).read()
        bad = src.replace("mxlint: requires(alpha_mu_)", "fixture:")
        import tempfile
        with tempfile.NamedTemporaryFile("w", suffix=".cc",
                                         delete=False) as tf:
            tf.write(bad)
        try:
            fs = native_lint.lint_file(tf.name, "native_fixture.cc",
                                       config=self.CFG)
            assert _rules(fs)["guarded-field"] == \
                _rules(findings)["guarded-field"] + 1
        finally:
            os.unlink(tf.name)

    def test_live_engine_discipline_is_machine_checked(self):
        """Deleting the engine.cc ~Engine lock reintroduces the
        missed-wakeup finding — the pass genuinely guards the fix
        shipped in this PR."""
        path = os.path.join(REPO_ROOT, "native/src/engine.cc")
        src = open(path).read()
        assert "std::lock_guard<std::mutex> lk(pool_mu_);\n" \
               "    stop_.store(true);" in src
        broken = src.replace(
            "    std::lock_guard<std::mutex> lk(pool_mu_);\n"
            "    stop_.store(true);", "    stop_.store(true);", 1)
        import tempfile
        with tempfile.NamedTemporaryFile("w", suffix=".cc",
                                         delete=False) as tf:
            tf.write(broken)
        try:
            fs = native_lint.lint_file(
                tf.name, "engine.cc",
                config=native_lint.CONFIG["engine.cc"])
            assert _rules(fs)["cv-pred-unlocked"] >= 1
        finally:
            os.unlink(tf.name)


class TestPylockFixtures:
    """Every pylocklint rule fires exactly as seeded in
    fixtures/mxlint/pylock_fixture.py, pragma twins stay suppressed,
    and the baseline suppresses by key (ISSUE 7 satellite)."""

    @pytest.fixture(scope="class")
    def findings(self):
        src = open(os.path.join(FIXTURES, "pylock_fixture.py")).read()
        return pylocklint.lint_source(src, "pylock_fixture.py")

    def test_counts(self, findings):
        assert _rules(findings) == {
            "py-guarded-field": 1,        # Guarded.bad
            "py-lock-order": 2,           # cycle + transitive re-acq
            "py-cv-wait-predicate": 1,    # CV.bare_wait
            "py-notify-unlocked": 1,      # CV.bad_notify
            "py-blocking-under-lock": 2,  # direct q.get + transitive
            "py-ref-leak": 3,             # return + exception + .refs
        }

    def test_lock_order_variants(self, findings):
        msgs = [f.message for f in findings
                if f.rule == "py-lock-order"]
        assert any("closes a lock-order cycle" in m for m in msgs)
        assert any("may re-acquire held non-reentrant" in m
                   for m in msgs)

    def test_blocking_variants(self, findings):
        msgs = [f.message for f in findings
                if f.rule == "py-blocking-under-lock"]
        assert any("queue.get" in m for m in msgs)
        assert any("call to _slow()" in m for m in msgs)

    def test_ref_leak_variants(self, findings):
        msgs = [f.message for f in findings if f.rule == "py-ref-leak"]
        assert any("exit without releasing" in m for m in msgs)
        assert any("exception edge leaks" in m for m in msgs)
        assert any("outside" in m for m in msgs)

    def test_pragma_suppressed_twins(self, findings):
        src = open(os.path.join(FIXTURES, "pylock_fixture.py")).read()
        lines = {(f.rule, f.line) for f in findings}
        for i, text in enumerate(src.splitlines(), 1):
            if "suppressed twin" in text:
                assert not any(ln in (i, i + 1, i + 2)
                               for _, ln in lines), \
                    "twin at line %d surfaced" % i

    def test_locked_convention_and_clean_shapes(self, findings):
        """helper_locked / guarded_exception / ok_escape / good_wait /
        good_notify / fine seeded NO findings."""
        import ast
        src = open(os.path.join(FIXTURES, "pylock_fixture.py")).read()
        spans = {}
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.FunctionDef):
                spans[node.name] = (node.lineno, node.end_lineno)
        clean = {"helper_locked", "guarded_exception", "ok_escape",
                 "good_wait", "good_notify", "fine"}
        for f in findings:
            for name in clean:
                lo, hi = spans[name]
                assert not (lo <= f.line <= hi), \
                    "%s seeded clean but got %s" % (name, f)

    def test_baseline_suppresses(self, findings):
        baseline = {f.key for f in findings
                    if f.rule == "py-guarded-field"}
        new, old = split_new(findings, baseline)
        assert _rules(old) == {"py-guarded-field": 1}
        assert "py-guarded-field" not in _rules(new)


class TestPylockAutoscalerCoverage:
    """ISSUE 11 satellite: pylocklint's guarded-field / lock-order
    inference reaches the round-16 ``serving/autoscaler.py`` (the
    live module's cleanliness is pinned by
    ``test_pylocklint_zero_findings_even_baselined``, which now scans
    it — these prove a violation planted THERE would fire, i.e. the
    coverage is real, not vacuous)."""

    def test_planted_guarded_field_fires(self):
        src = ("import threading\n"
               "class Autoscaler:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "        self.target = 0\n"
               "    def tick(self):\n"
               "        with self._mu:\n"
               "            self.target = 1\n"
               "    def _loop(self):\n"
               "        self.target = 2\n")
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/autoscaler.py")
        assert _rules(fs) == {"py-guarded-field": 1}

    def test_planted_lock_order_cycle_fires(self):
        src = ("import threading\n"
               "class Autoscaler:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "        self._scale_mu = threading.Lock()\n"
               "    def tick(self):\n"
               "        with self._mu:\n"
               "            with self._scale_mu:\n"
               "                pass\n"
               "    def _loop(self):\n"
               "        with self._scale_mu:\n"
               "            with self._mu:\n"
               "                pass\n")
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/autoscaler.py")
        assert "py-lock-order" in _rules(fs)

    def test_planted_blocking_under_lock_fires(self):
        # the autoscaler's real hazard shape: actuation (a blocking
        # drain) while holding a lock
        src = ("import threading, time\n"
               "class Autoscaler:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "    def tick(self):\n"
               "        with self._mu:\n"
               "            time.sleep(1.0)\n")
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/autoscaler.py")
        assert _rules(fs) == {"py-blocking-under-lock": 1}


class TestPylockTierCoverage:
    """ISSUE 13 satellite: pylocklint's auto-scope reaches the
    round-18 ``serving/tier_store.py`` (zero findings on the live
    module is pinned by the repo-wide scan; these prove a violation
    planted THERE would fire — the coverage is real, not vacuous.
    The live store is deliberately lock-free on the owning engine's
    thread, so the plants are the shapes a future 'make it shared'
    edit would introduce)."""

    def test_planted_guarded_field_fires(self):
        src = ("import threading\n"
               "class HostTierStore:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "        self.bytes_held = 0\n"
               "    def put(self, n):\n"
               "        with self._mu:\n"
               "            self.bytes_held = n\n"
               "    def pop(self):\n"
               "        self.bytes_held = 0\n")
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/tier_store.py")
        assert _rules(fs) == {"py-guarded-field": 1}

    def test_planted_blocking_under_lock_fires(self):
        # the tier's real future hazard shape: a device transfer
        # (blocking) while holding a store lock would serialize every
        # spill behind every restore
        src = ("import threading, time\n"
               "class HostTierStore:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "    def put(self, key):\n"
               "        with self._mu:\n"
               "            time.sleep(0.1)\n")
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/tier_store.py")
        assert _rules(fs) == {"py-blocking-under-lock": 1}


class TestPylockOverlapCoverage:
    """Round 21: pylocklint genuinely covers the double-buffered
    planner handoff in ``serving/engine.py`` (the live module's
    cleanliness is pinned by the repo-wide zero-findings scan; these
    prove the violations the overlap pipeline COULD regress into
    would fire there — coverage is real, not vacuous)."""

    def test_planted_plan_state_unguarded_write_fires(self):
        # the handoff hazard: the planner publishes plan state under
        # the engine lock, so a step-side write that skips the lock
        # is exactly the torn-handoff bug the discipline prevents
        src = ("import threading\n"
               "class ServingEngine:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "        self._buf_idx = 0\n"
               "    def _build_plan(self):\n"
               "        with self._mu:\n"
               "            self._buf_idx ^= 1\n"
               "    def _reset(self):\n"
               "        with self._mu:\n"
               "            self._buf_idx = 0\n"
               "    def step(self):\n"
               "        self._buf_idx ^= 1\n")
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/engine.py")
        assert _rules(fs) == {"py-guarded-field": 1}

    def test_planted_ready_wait_under_lock_fires(self):
        # the deadlock shape the handoff must never regress into:
        # step() waiting for the planner's ready event WHILE holding
        # the lock the planner needs to build the plan
        src = ("import threading\n"
               "class ServingEngine:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "        self._plan_ready = threading.Event()\n"
               "    def _take_plan(self):\n"
               "        with self._mu:\n"
               "            self._plan_ready.wait()\n")
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/engine.py")
        assert _rules(fs) == {"py-blocking-under-lock": 1}

    def test_planted_dispatch_under_lock_fires(self):
        # dispatching the jitted step while holding the engine lock
        # would stall submit/cancel behind device time — the exact
        # latency the overlap exists to hide
        src = ("import threading\n"
               "class ServingEngine:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "    def _dispatch(self, plan):\n"
               "        with self._mu:\n"
               "            self._step_fn(plan)\n")
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/engine.py")
        assert _rules(fs) == {"py-blocking-under-lock": 1}

    def test_live_requires_pragmas_are_load_bearing(self):
        """Stripping the ``requires(ServingEngine._mu)`` pragmas from
        the live engine makes guarded-field findings appear: the
        plan/commit helpers really do touch lock-guarded state,
        and the pragmas are the proof obligation, not decoration."""
        path = os.path.join(REPO_ROOT, "mxnet_tpu/serving/engine.py")
        src = open(path).read()
        assert src.count("mxlint: requires(ServingEngine._mu)") >= 4
        stripped = src.replace(
            "# mxlint: requires(ServingEngine._mu)", "#")
        fs = pylocklint.lint_source(
            stripped, "mxnet_tpu/serving/engine.py")
        assert _rules(fs).get("py-guarded-field", 0) >= 1

    def test_planted_lock_order_cycle_fires(self):
        src = ("import threading\n"
               "class HostTierStore:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "        self._lru_mu = threading.Lock()\n"
               "    def put(self):\n"
               "        with self._mu:\n"
               "            with self._lru_mu:\n"
               "                pass\n"
               "    def evict(self):\n"
               "        with self._lru_mu:\n"
               "            with self._mu:\n"
               "                pass\n")
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/tier_store.py")
        assert "py-lock-order" in _rules(fs)


class TestPylockKVStoreCoverage:
    """ISSUE 14 satellite: pylocklint's auto-scope reaches the
    round-19 ``mxnet_tpu/kvstore`` package (the ICI-allreduce store's
    telemetry counters are written under ``self._mu`` from whatever
    thread pushes; zero findings on the live package is pinned by
    ``test_pylocklint_zero_findings_even_baselined``, which now scans
    it — these prove a violation planted THERE would fire, i.e. the
    coverage is real, not vacuous)."""

    def test_planted_guarded_field_fires(self):
        src = ("import threading\n"
               "class ICIKVStore:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "        self._collectives = 0\n"
               "    def push(self, key, value):\n"
               "        with self._mu:\n"
               "            self._collectives += 1\n"
               "    def reset(self):\n"
               "        self._collectives = 0\n")
        fs = pylocklint.lint_source(src, "mxnet_tpu/kvstore/ici.py")
        assert _rules(fs) == {"py-guarded-field": 1}

    def test_planted_blocking_under_lock_fires(self):
        # the store's real hazard shape: dispatching the collective
        # (a device step) while holding the telemetry lock would
        # serialize every pushing thread behind the compiled program
        src = ("import threading, time\n"
               "class ICIKVStore:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "    def push(self, key, value):\n"
               "        with self._mu:\n"
               "            time.sleep(0.5)\n")
        fs = pylocklint.lint_source(src, "mxnet_tpu/kvstore/ici.py")
        assert _rules(fs) == {"py-blocking-under-lock": 1}

    def test_live_store_holds_no_lock_across_the_collective(self):
        """The live push() dispatches the collective OUTSIDE _mu (the
        lock guards only the counters) — pinned here so a refactor
        that hoists the lock around _reduce_flat re-fires the planted
        shape above on the real file."""
        src = open(os.path.join(
            REPO_ROOT, "mxnet_tpu/kvstore/ici.py")).read()
        fs = pylocklint.lint_source(src, "mxnet_tpu/kvstore/ici.py")
        assert fs == [], [str(f) for f in fs]


class TestPylockHttpFrontendCoverage:
    """ISSUE 15 satellite: pylocklint's auto-scope (the
    ``mxnet_tpu/serving`` package glob) reaches the round-20
    ``http_frontend.py`` — the thread↔asyncio bridge is exactly its
    beat: cluster threads feed the event loop via
    ``call_soon_threadsafe`` while the loop thread owns quota state.
    Zero findings on the live module is pinned below; the planted
    shapes prove a violation THERE would fire — coverage is real, not
    vacuous."""

    def test_planted_guarded_field_fires(self):
        src = ("import threading\n"
               "class HttpFrontend:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "        self._active = 0\n"
               "    def _serve_conn(self, reader, writer):\n"
               "        with self._mu:\n"
               "            self._active += 1\n"
               "    def close(self):\n"
               "        self._active = 0\n")
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/http_frontend.py")
        assert _rules(fs) == {"py-guarded-field": 1}

    def test_planted_blocking_under_lock_fires(self):
        # the front door's real hazard shape: waiting on the cluster
        # (a blocking result()/submit()) while holding a lock the
        # completion callback needs would deadlock every stream —
        # the live module routes ALL cluster calls through the
        # executor and keeps quota state loop-thread-only
        src = ("import threading, time\n"
               "class HttpFrontend:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "    def _run_request(self, rid):\n"
               "        with self._mu:\n"
               "            time.sleep(0.5)\n")
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/http_frontend.py")
        assert _rules(fs) == {"py-blocking-under-lock": 1}

    def test_live_frontend_is_clean(self):
        """The live module holds no lock across any blocking call
        (the bridge is one ``call_soon_threadsafe`` per event batch;
        cluster calls ride the executor) — pinned so a refactor that
        adds a lock around the bridge re-fires the planted shapes
        above on the real file."""
        src = open(os.path.join(
            REPO_ROOT, "mxnet_tpu/serving/http_frontend.py")).read()
        fs = pylocklint.lint_source(
            src, "mxnet_tpu/serving/http_frontend.py")
        assert fs == [], [str(f) for f in fs]


class TestPylockObsFlightCoverage:
    """Round 23 satellite: pylocklint covers the crash-durable flight
    ring and the worker span buffer — both emit from HOT paths (wire
    recv threads, the engine step loop), so their locks must stay
    memory-only.  Zero findings on the live ``mxnet_tpu/obs`` package
    is pinned by the repo-wide scan; the plants prove the violations
    the observability layer COULD regress into would fire there."""

    def test_planted_flight_sync_under_lock_fires(self):
        # THE tempting flight-ring bug: "make it durable" by msync
        # (or any syscall) inside record()'s lock — every wire recv
        # and engine step would then serialize behind a disk flush.
        # Page-cache durability is the design; a sync is a regression.
        src = ("import threading, time\n"
               "class FlightRecorder:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "    def record(self, kind):\n"
               "        with self._lock:\n"
               "            time.sleep(0)\n")
        fs = pylocklint.lint_source(src, "mxnet_tpu/obs/flight.py")
        assert _rules(fs) == {"py-blocking-under-lock": 1}

    def test_planted_span_ship_under_lock_fires(self):
        # the span-shipping hazard: draining the buffer is fine, but
        # waiting for the router's ship ack while still holding the
        # buffer lock would stall every concurrent span/instant emit
        # behind the socket round-trip — the live worker drains under
        # the lock, ships outside
        src = ("import threading\n"
               "class SpanBuffer:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n"
               "        self._acked = threading.Event()\n"
               "    def ship(self):\n"
               "        with self._mu:\n"
               "            self._acked.wait()\n")
        fs = pylocklint.lint_source(src, "mxnet_tpu/obs/trace.py")
        assert _rules(fs) == {"py-blocking-under-lock": 1}

    def test_planted_guarded_seq_fires(self):
        # the ring's seq counter is lock-guarded (slot index and slot
        # head derive from it); an unguarded fast-path increment is a
        # torn-slot generator under concurrent recorders
        src = ("import threading\n"
               "class FlightRecorder:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self._seq = 0\n"
               "    def record(self, kind):\n"
               "        with self._lock:\n"
               "            self._seq += 1\n"
               "    def reset(self):\n"
               "        self._seq = 0\n")
        fs = pylocklint.lint_source(src, "mxnet_tpu/obs/flight.py")
        assert _rules(fs) == {"py-guarded-field": 1}

    def test_live_obs_emit_paths_are_clean(self):
        """The live recorder/buffer/merger hold their locks over
        memory-only work (json.dumps + buffer stores; the profiler
        hand-off is a locked list append) — pinned so a refactor that
        adds a flush or a send under either lock re-fires the planted
        shapes on the real files."""
        for rel in ("mxnet_tpu/obs/flight.py",
                    "mxnet_tpu/obs/trace.py"):
            src = open(os.path.join(REPO_ROOT, rel)).read()
            fs = pylocklint.lint_source(src, rel)
            assert fs == [], (rel, [str(f) for f in fs])


class TestBenchSyncFixtures:
    """jaxlint bench-no-sync (ISSUE 7 satellite): the timed-region /
    unsynced-jit pattern fires once, the pragma'd twin is suppressed,
    proper syncs (direct or via a local hard_sync-style helper) stay
    clean."""

    SRC = (
        "import time\n"
        "import jax\n"
        "import numpy as np\n"
        "\n"
        "\n"
        "def hard_sync(r):\n"
        "    jax.block_until_ready(r)\n"
        "\n"
        "\n"
        "def bad(f, x):\n"
        "    g = jax.jit(f)\n"
        "    t0 = time.perf_counter()\n"
        "    r = g(x)\n"
        "    dt = time.perf_counter() - t0\n"
        "    return r, dt\n"
        "\n"
        "\n"
        "def bad_bare_close(f, x):\n"
        "    g = jax.jit(f)\n"
        "    t0 = time.perf_counter()\n"
        "    r = g(x)\n"
        "    t1 = time.perf_counter()\n"
        "    return r, t1 - t0\n"
        "\n"
        "\n"
        "def bad_twin(f, x):\n"
        "    g = jax.jit(f)\n"
        "    t0 = time.perf_counter()\n"
        "    r = g(x)\n"
        "    # mxlint: allow(bench-no-sync) -- suppressed twin\n"
        "    dt = time.perf_counter() - t0\n"
        "    return r, dt\n"
        "\n"
        "\n"
        "def good_direct(f, x):\n"
        "    g = jax.jit(f)\n"
        "    t0 = time.perf_counter()\n"
        "    r = g(x)\n"
        "    jax.block_until_ready(r)\n"
        "    dt = time.perf_counter() - t0\n"
        "    return dt\n"
        "\n"
        "\n"
        "def good_helper(f, x):\n"
        "    g = jax.jit(f)\n"
        "    t0 = time.perf_counter()\n"
        "    hard_sync(g(x))\n"
        "    dt = time.perf_counter() - t0\n"
        "    return dt\n"
        "\n"
        "\n"
        "def good_loop(f, x):\n"
        "    g = jax.jit(f)\n"
        "    best = 1e9\n"
        "    for _ in range(3):\n"
        "        t0 = time.perf_counter()\n"
        "        r = g(x)\n"
        "        r = np.asarray(r)\n"
        "        best = min(best, time.perf_counter() - t0)\n"
        "    return best\n"
        "\n"
        "\n"
        "def untimed(f, x):\n"
        "    g = jax.jit(f)\n"
        "    return g(x)\n")

    @pytest.fixture(scope="class")
    def findings(self):
        return jaxlint.lint_source(self.SRC, "bench_fixture.py",
                                   region_re="$^", clock=False,
                                   bench=True)

    def test_fires_exactly_once_per_seed(self, findings):
        """One finding per seeded region: the subtraction close (bad)
        and the bare `t1 = perf_counter()` close (bad_bare_close —
        the canonical two-read idiom, a review-pass fix)."""
        assert _rules(findings) == {"bench-no-sync": 2}
        assert "line 13" in findings[0].message

    def test_engine_methods_do_not_alias_jitted_names(self):
        """`eng.run()` must not match a local `@jax.jit def run` —
        the spec_decode_probe false positive fixed in this PR."""
        src = ("import time\nimport jax\n"
               "@jax.jit\n"
               "def run(x):\n"
               "    return x\n"
               "def bench(eng, x):\n"
               "    t0 = time.perf_counter()\n"
               "    outs = eng.run()\n"
               "    return time.perf_counter() - t0\n")
        fs = jaxlint.lint_source(src, "b.py", region_re="$^",
                                 clock=False, bench=True)
        assert fs == []

    def test_live_benchmarks_clean(self):
        """Every benchmark driver syncs what it times (or pragmas the
        dispatch measurement) — zero live findings."""
        bench_dir = os.path.join(REPO_ROOT, "benchmark")
        bad = []
        for name in sorted(os.listdir(bench_dir)):
            if not name.endswith(".py"):
                continue
            src = open(os.path.join(bench_dir, name)).read()
            bad += [f for f in jaxlint.lint_source(
                src, "benchmark/" + name)
                if f.rule == "bench-no-sync"]
        assert bad == [], "\n".join(str(f) for f in bad)


class TestHotRegionAdditions:
    """ISSUE 7 satellite: the round-12 hot regions — cluster
    watchdog/failover, prefix-cache eviction/COW leaf, metrics
    registry mutation — each trip on a planted violation exactly once,
    and a violation OUTSIDE the region stays silent."""

    PLANT = ("    import jax\n"
             "    for _ in range(2):\n"
             "        f = jax.jit(lambda x: x)\n")

    CASES = [
        ("mxnet_tpu/serving/cluster.py",
         "class ServingCluster:\n"
         " def _fail_replica(self, rep, error):\n%s"),
        ("mxnet_tpu/serving/cluster.py",
         "class ServingCluster:\n"
         " def _monitor_loop(self):\n%s"),
        ("mxnet_tpu/serving/cluster.py",
         "class ServingCluster:\n"
         " def drain_replica(self, idx):\n%s"),
        ("mxnet_tpu/serving/prefix_cache.py",
         "class PrefixCache:\n"
         " def _drop(self, e):\n%s"),
        ("mxnet_tpu/obs/metrics.py",
         "class MetricsRegistry:\n"
         " def _get(self, cls, name):\n%s"),
        # round 16: the autoscaler control loop, the chaos driver's
        # replay-time apply path, and the trace generator
        ("mxnet_tpu/serving/autoscaler.py",
         "class Autoscaler:\n"
         " def tick(self, now=None):\n%s"),
        ("mxnet_tpu/serving/chaos.py",
         "class ChaosDriver:\n"
         " def poll(self, now_rel):\n%s"),
        ("benchmark/traffic_trace.py",
         "def generate_trace(spec):\n%s"),
        # round 17: the disagg scale-actuation paths protolint's
        # call-graph walks also cover — add_worker/drain_worker and
        # the late-join handshake helper run while the cluster serves
        ("mxnet_tpu/serving/cluster.py",
         "class DisaggServingCluster:\n"
         " def add_worker(self, role):\n%s"),
        ("mxnet_tpu/serving/cluster.py",
         "class DisaggServingCluster:\n"
         " def drain_worker(self, name):\n%s"),
        ("mxnet_tpu/serving/cluster.py",
         "class DisaggServingCluster:\n"
         " def _handshake_one(self, wh, timeout):\n%s"),
        # round 18: the KV-tiering hot paths — the whole tier store,
        # the prefix-cache spill/restore leaves (they run inside the
        # allocator's pressure callback), and the engine's swap
        # paths; an in-loop jit or stray sync there prices every
        # pressure event and every preemption resume
        ("mxnet_tpu/serving/tier_store.py",
         "class HostTierStore:\n"
         " def put(self, key, content, n_pages):\n%s"),
        ("mxnet_tpu/serving/prefix_cache.py",
         "class PrefixCache:\n"
         " def _spill_entry(self, e):\n%s"),
        ("mxnet_tpu/serving/prefix_cache.py",
         "class PrefixCache:\n"
         " def _restore_run(self, tokens, m, parent):\n%s"),
        ("mxnet_tpu/serving/engine.py",
         "class ServingEngine:\n"
         " def _preempt_victim(self, victim):\n%s"),
        ("mxnet_tpu/serving/engine.py",
         "class ServingEngine:\n"
         " def _swap_in(self, req, inp, slot):\n%s"),
        # round 19: the training scale-out hot paths — the ICI
        # KVStore's per-gradient-sync push/bucketing and the FSDP
        # composition helpers traced inside the sharded train step;
        # an in-loop jit there recompiles the collective every sync
        ("mxnet_tpu/kvstore/ici.py",
         "class ICIKVStore:\n"
         " def push(self, key, value, priority=0):\n%s"),
        ("mxnet_tpu/kvstore/ici.py",
         "class ICIKVStore:\n"
         " def _reduce_flat(self, devs, bucket):\n%s"),
        ("mxnet_tpu/parallel/fsdp.py",
         "def fsdp_param_specs(cfg, dp='dp', tp=None):\n%s"),
        # round 20: the HTTP front door's streaming/cancel paths run
        # on the ONE asyncio event loop thread — an in-loop jit or
        # stray sync in the SSE pump or the disconnect→cancel path
        # stalls every open stream at once
        ("mxnet_tpu/serving/http_frontend.py",
         "class HttpFrontend:\n"
         " async def _stream_sse(self, writer, reader, q, rid, "
         "prompt, req_id):\n%s"),
        ("mxnet_tpu/serving/http_frontend.py",
         "class HttpFrontend:\n"
         " async def _cancel_disconnected(self, rid):\n%s"),
        ("benchmark/http_bench.py",
         "def run_load(args):\n%s"),
        # round 24: the round-23 debug endpoints run on the same
        # event-loop thread as every SSE stream — an in-loop jit in
        # statusz/trace handling stalls all of them at once
        ("mxnet_tpu/serving/http_frontend.py",
         "class HttpFrontend:\n"
         " async def _handle_statusz(self, writer, req_id):\n%s"),
        ("mxnet_tpu/serving/http_frontend.py",
         "class HttpFrontend:\n"
         " async def _handle_trace(self, writer, path, req_id):\n%s"),
    ]

    @pytest.mark.parametrize("rel,template", CASES)
    def test_planted_violation_fires_once(self, rel, template):
        src = template % self.PLANT.replace("    ", "  ")
        fs = jaxlint.lint_source(src, rel, clock=False)
        assert _rules(fs) == {"retrace": 1}, \
            "%s: %r" % (rel, [str(f) for f in fs])

    def test_outside_region_is_silent(self):
        src = ("class ServingCluster:\n"
               " def some_cold_path(self):\n"
               "  import jax\n"
               "  for _ in range(2):\n"
               "   f = jax.jit(lambda x: x)\n")
        fs = jaxlint.lint_source(src, "mxnet_tpu/serving/cluster.py",
                                 clock=False)
        assert fs == []


# ---------------------------------------------------------------------------
# protolint (ISSUE 12): live repo, fixtures, protocol audit workflow
# ---------------------------------------------------------------------------
def _serving_modules():
    return protolint._load_modules(REPO_ROOT)


def _with_cluster(src):
    mods = _serving_modules()
    mods["mxnet_tpu/serving/cluster.py"] = src
    return mods


class TestProtolintLiveRepo:
    def test_protolint_zero_findings_even_baselined(self):
        """ISSUE 12 acceptance criterion: the wire-protocol &
        process-lifecycle audit reports ZERO findings with an EMPTY
        baseline over mxnet_tpu/serving/ — nothing grandfathered."""
        fs = protolint.run(REPO_ROOT)
        assert fs == [], "\n".join(str(f) for f in fs)

    def test_protocol_audit_checked_in_and_current(self):
        """docs/protocol.md is committed (acceptance criterion) and
        regenerates identically; every conn.send kind in serving/ has
        a handler row (no UNCOVERED), and the gen-fenced kinds are
        marked."""
        path = os.path.join(REPO_ROOT, protolint.AUDIT_PATH)
        committed = open(path).read()
        assert committed == protolint.protocol_audit_md(REPO_ROOT)
        assert "UNCOVERED" not in committed
        for kind in ("submit", "pages", "handoff", "fetch",
                     "fetch_reply", "stats_req", "stats", "abort",
                     "tokens", "done", "hello", "ready", "config",
                     "peers", "shutdown", "cancel"):
            assert "| `%s` |" % kind in committed, kind
        # the gen-fence column is verified, not decorative
        assert "| NO |" not in committed

    def test_cancel_kind_is_gen_fenced(self):
        """ISSUE 15: the round-20 client-disconnect ``cancel`` wire
        kind is audited — router → worker, carrying ``below_gen`` —
        and the fence column says yes, so a late cancel for a gen
        that already died is a no-op by checked invariant, not by
        convention."""
        committed = open(os.path.join(REPO_ROOT,
                                      protolint.AUDIT_PATH)).read()
        row = next(ln for ln in committed.splitlines()
                   if ln.startswith("| `cancel` |"))
        assert "router → worker" in row
        assert "below_gen" in row
        assert row.rstrip().endswith("| yes |")
        # synthetic in-process kinds never reach the wire table
        assert "| `_wake` |" not in committed
        assert "| `_lost` |" not in committed

    def test_audit_covers_every_send_kind(self):
        """The table covers exactly the literal-kind send sites the
        model sees — a new conn.send kind cannot ship without a row
        (and, via tier-1, without a handler)."""
        committed = open(os.path.join(
            REPO_ROOT, protolint.AUDIT_PATH)).read()
        prog = protolint.build_model(_serving_modules())
        kinds = {s.kind for s in prog.sends
                 if not s.kind.startswith("_")}
        assert kinds, "protocol model saw no send sites"
        for kind in kinds:
            assert "| `%s` |" % kind in committed, kind

    def test_protolint_guards_the_submit_gen_fence(self):
        """Deleting the round-17 fence in the worker's submit arm
        re-fires proto-gen-fence — the pass genuinely guards the fix
        shipped in this PR (PR-4/7/8 convention)."""
        src = _serving_modules()["mxnet_tpu/serving/cluster.py"]
        fence = (
            '            if meta["gen"] < self._fenced.get('
            'meta["rid"], -1):\n'
            "                # a late dispatch racing an abort for a "
            "NEWER\n"
            "                # incarnation of the same rid: the "
            "router no longer\n"
            "                # wants this gen — admitting it would "
            "resurrect a\n"
            "                # fenced zombie (proto-gen-fence checked "
            "invariant)\n"
            "                return\n")
        assert fence in src
        fs = protolint.analyze(_with_cluster(src.replace(fence, "",
                                                         1)))
        got = [f for f in fs if f.rule == "proto-gen-fence"
               and f.symbol == "submit"]
        assert len(got) == 1, [str(f) for f in fs]

    def test_protolint_guards_the_fetch_reply_degrade(self):
        """The fetch server's degrade-to-miss handler is what makes
        the fetch/fetch_reply pairing hold on exception edges —
        replacing it with a re-raise re-fires proto-reply-pairing."""
        src = _serving_modules()["mxnet_tpu/serving/cluster.py"]
        handler = (
            "            except Exception:\n"
            "                # degrade to a miss: the requester falls "
            "back to a\n"
            "                # cold prefill instead of eating its "
            "fetch timeout\n"
            "                n_full, reply_bufs = 0, []\n")
        assert handler in src
        broken = src.replace(
            handler, "            except Exception:\n"
                     "                raise\n", 1)
        fs = protolint.analyze(_with_cluster(broken))
        got = [f for f in fs if f.rule == "proto-reply-pairing"
               and f.symbol == "fetch"]
        assert len(got) == 1, [str(f) for f in fs]

    def test_protolint_guards_the_stats_reply_path(self):
        """_send_stats is the stats_req reply path: reintroducing the
        pre-round-17 rate-limit early-return re-fires
        proto-reply-pairing (a rate-limited reply DROPS solicited
        replies and stalls cluster_stats() to its timeout)."""
        src = _serving_modules()["mxnet_tpu/serving/cluster.py"]
        entry = ("        self._last_stats = time.perf_counter()\n"
                 "        eng = self.eng\n")
        assert entry in src
        broken = src.replace(entry, (
            "        if sid is None:\n"
            "            return\n" + entry), 1)
        fs = protolint.analyze(_with_cluster(broken))
        got = [f for f in fs if f.rule == "proto-reply-pairing"
               and f.symbol == "stats_req"]
        assert len(got) == 1, [str(f) for f in fs]

    def test_protolint_guards_the_terminate_reap_fixes(self):
        """Dropping any of the round-17 post-terminate joins re-fires
        py-resource-lifecycle: a SIGTERMed worker process stays a
        zombie pid until the router exits."""
        src = _serving_modules()["mxnet_tpu/serving/cluster.py"]
        reap = "                wh.proc.join(timeout=5)   " \
               "# reap the zombie pid\n"
        assert reap in src
        fs = protolint.analyze(_with_cluster(src.replace(reap, "",
                                                         1)))
        got = [f for f in fs if f.rule == "py-resource-lifecycle"
               and f.symbol == "terminate"]
        assert len(got) == 1, [str(f) for f in fs]

    def test_protolint_catches_meta_schema_drift(self):
        """Dropping a meta key one side still reads fires
        proto-meta-schema at the drifted SEND site — the cross-process
        KeyError class the rule exists for."""
        src = _serving_modules()["mxnet_tpu/serving/cluster.py"]
        whole = ('self.router.send("lost", {"rid": st["rid"],\n'
                 '                                      '
                 '"gen": st["gen"]})')
        assert whole in src
        broken = src.replace(
            whole, 'self.router.send("lost", {"rid": st["rid"]})', 1)
        fs = protolint.analyze(_with_cluster(broken))
        got = [f for f in fs if f.rule == "proto-meta-schema"]
        assert len(got) == 1 and got[0].symbol == "lost" \
            and "'gen'" in got[0].message, [str(f) for f in fs]

    def test_protolint_catches_dropped_dispatch_arm(self):
        """Deleting a dispatch arm fires proto-unhandled-kind at the
        send site — the silent-drop class."""
        src = _serving_modules()["mxnet_tpu/serving/cluster.py"]
        arm = ('            elif kind == "handed":\n'
               "                self._on_handed(wh, meta)\n")
        assert arm in src
        fs = protolint.analyze(_with_cluster(src.replace(arm, "", 1)))
        got = [f for f in fs if f.rule == "proto-unhandled-kind"]
        assert len(got) == 1 and got[0].symbol == "handed", \
            [str(f) for f in fs]

    def test_changed_only_trigger_gating(self, monkeypatch):
        """--changed-only: protolint re-analyzes only when serving/,
        parallel/dist.py, or tools/analysis/ change; any other change
        set skips the pass entirely (and a triggered run reports only
        changed files, pylocklint's convention)."""
        assert protolint.triggered(None)
        assert protolint.triggered({"mxnet_tpu/serving/cluster.py"})
        assert protolint.triggered({"mxnet_tpu/parallel/dist.py"})
        assert protolint.triggered({"tools/analysis/protolint.py"})
        assert not protolint.triggered({"README.md",
                                        "mxnet_tpu/models/gpt.py"})

        def boom(*a, **kw):
            raise AssertionError("analyzed despite no trigger")

        monkeypatch.setattr(protolint, "analyze", boom)
        assert protolint.run(REPO_ROOT, only={"README.md"}) == []


class TestProtoFixtures:
    """Every protolint rule fires exactly once as seeded in
    fixtures/mxlint/proto_fixture.py, pragma twins stay suppressed,
    clean shapes stay silent, and the baseline suppresses by key
    (ISSUE 12 satellite, mirroring pylock_fixture.py)."""

    ROLES = {"FixRouter": "router", "FixWorker": "worker"}

    @pytest.fixture(scope="class")
    def findings(self):
        src = open(os.path.join(FIXTURES, "proto_fixture.py")).read()
        return protolint.lint_source(src, "proto_fixture.py",
                                     roles=self.ROLES)

    def test_each_rule_fires_exactly_once(self, findings):
        assert _rules(findings) == {
            "proto-unhandled-kind": 1,    # orphan send site
            "proto-unknown-kind": 1,      # ghost arm
            "proto-meta-schema": 1,       # job missing payload
            "proto-gen-fence": 1,         # cancel arm unfenced
            "proto-reply-pairing": 1,     # ping_req exception edge
            "py-resource-lifecycle": 1,   # leaked Listener
        }, [str(f) for f in findings]

    def test_findings_name_their_kinds(self, findings):
        by_rule = {f.rule: f for f in findings}
        assert by_rule["proto-unhandled-kind"].symbol == "orphan"
        assert by_rule["proto-unknown-kind"].symbol == "ghost"
        assert by_rule["proto-meta-schema"].symbol == "job"
        assert "'payload'" in by_rule["proto-meta-schema"].message
        assert by_rule["proto-gen-fence"].symbol == "cancel"
        assert by_rule["proto-reply-pairing"].symbol == "ping_req"
        assert by_rule["py-resource-lifecycle"].symbol == "lst"

    def test_pragma_suppressed_twins(self, findings):
        src = open(os.path.join(FIXTURES, "proto_fixture.py")).read()
        lines = {(f.rule, f.line) for f in findings}
        hit = 0
        for i, text in enumerate(src.splitlines(), 1):
            if "suppressed twin" in text:
                hit += 1
                assert not any(ln in (i, i + 1, i + 2)
                               for _, ln in lines), \
                    "twin at line %d surfaced" % i
        assert hit >= 6                   # one twin per rule (+ the
        #                                   docstring's mentions)

    def test_clean_shapes_silent(self, findings):
        """The fenced arm (fine), the replying pair twin (echo_req),
        the escaping connection, the daemon thread, and the
        terminate+join pair seeded NO findings."""
        import ast
        src = open(os.path.join(FIXTURES, "proto_fixture.py")).read()
        spans = {}
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.FunctionDef):
                spans[node.name] = (node.lineno, node.end_lineno)
        for f in findings:
            for name in ("send_fine", "recv_loop", "clean_escape",
                         "clean_daemon_thread", "clean_reaped"):
                lo, hi = spans[name]
                assert not (lo <= f.line <= hi), \
                    "%s seeded clean but got %s" % (name, f)

    def test_baseline_suppresses(self, findings):
        baseline = {f.key for f in findings
                    if f.rule == "proto-gen-fence"}
        new, old = split_new(findings, baseline)
        assert _rules(old) == {"proto-gen-fence": 1}
        assert "proto-gen-fence" not in _rules(new)


class TestProtolintWalkerEdges:
    """Review-pass regressions: walker edge cases that would each be
    a silent false negative (the zero-findings bar leans on the
    analyzer actually looking)."""

    PROBE = (
        "class W:\n"
        "    def __init__(self, router):\n"
        "        self.router = router\n"
        "    def handle(self, kind, meta, bufs):\n"
        "        if kind == 'ping_req':\n"
        "%s"
        "class R:\n"
        "    def __init__(self, conn):\n"
        "        self.conn = conn\n"
        "    def go(self):\n"
        "        self.conn.send('ping_req', {'q': 1})\n"
        "    def recv_loop(self):\n"
        "        kind, meta, bufs = self.conn.recv()\n"
        "        if kind == 'ping':\n"
        "            pass\n")
    ROLES = {"R": "router", "W": "worker"}

    def _lint(self, arm_body):
        return protolint.lint_source(self.PROBE % arm_body, "m.py",
                                     roles=self.ROLES)

    def test_last_arm_in_chain_is_exit_edge_checked(self):
        """An arm whose whole If fits the arm span (the LAST arm of
        an elif chain) must still get branch analysis — reordering
        _handle must never silently disable the reply check."""
        fs = self._lint(
            "            data = self.compute(meta['q'])\n"
            "            self.router.send('ping', {'rid': data})\n")
        assert _rules(fs) == {"proto-reply-pairing": 1}

    def test_reply_in_one_branch_does_not_cover_the_other(self):
        """`if ok: send_reply()` / `else: return` drops the reply on
        the else edge — containment alone must not settle it."""
        fs = self._lint(
            "            if meta.get('ok', 0):\n"
            "                self.router.send('ping', {'rid': 1})\n"
            "            else:\n"
            "                return\n")
        assert _rules(fs) == {"proto-reply-pairing": 1}

    def test_bare_try_finally_does_not_protect(self):
        """try/finally without a handler does not stop the exception
        — the reply is still dropped on that edge."""
        fs = self._lint(
            "            try:\n"
            "                data = self.compute(meta['q'])\n"
            "            finally:\n"
            "                self.cleanup()\n"
            "            self.router.send('ping', {'rid': data})\n")
        assert _rules(fs) == {"proto-reply-pairing": 1}

    def test_fall_through_exit_leaks_resource(self):
        """The implicit function-end exit is an exit path too: an
        acquired resource that is never settled must flag even with
        no explicit return."""
        fs = protolint.lint_source(
            "class C:\n"
            "    def f(self):\n"
            "        lst = Listener()\n", "m.py", roles={})
        assert _rules(fs) == {"py-resource-lifecycle": 1}

    def test_settle_in_block_continuation_is_clean(self):
        """A resource acquired inside an `if` and settled after it
        (the _peer_conn shape) must NOT flag on the if-body's end."""
        fs = protolint.lint_source(
            "class C:\n"
            "    def f(self, cached):\n"
            "        conn = cached\n"
            "        if conn is None:\n"
            "            conn = connect('h', 1)\n"
            "        self.conns[0] = conn\n"
            "        return conn\n", "m.py", roles={})
        assert fs == [], [str(f) for f in fs]


# ---------------------------------------------------------------------------
# asynclint (ISSUE 19): live repo, forced-fix guards, fixtures
# ---------------------------------------------------------------------------
HTTP_FRONTEND = "mxnet_tpu/serving/http_frontend.py"


class TestAsynclintLiveRepo:
    def test_asynclint_zero_findings_even_baselined(self):
        """ISSUE 19 acceptance criterion: the asyncio event-loop
        audit reports ZERO findings with an EMPTY baseline over
        serving/ + obs/ — nothing grandfathered."""
        fs = asynclint.run(REPO_ROOT)
        assert fs == [], "\n".join(str(f) for f in fs)

    def test_asynclint_guards_the_503_wait_closed_fix(self):
        """The forced fix, edge 1: the 503 connection-cap path must
        drain the refused transport (close() only schedules the
        close).  Reverting it to the bare close()+return re-fires
        async-writer-lifecycle on that exit edge."""
        src = open(os.path.join(REPO_ROOT, HTTP_FRONTEND)).read()
        fix = (
            "            writer.close()\n"
            "            try:\n"
            "                # close() only schedules the close — "
            "wait for the\n"
            "                # transport to drain so refused "
            "connections can't\n"
            "                # pile up half-closed under an overload "
            "burst\n"
            "                await writer.wait_closed()\n"
            "            except OSError:\n"
            "                pass\n"
            "            return")
        assert fix in src
        broken = src.replace(
            fix, "            writer.close()\n            return", 1)
        fs = [f for f in asynclint.lint_source(broken, HTTP_FRONTEND)
              if f.rule == "async-writer-lifecycle"]
        assert len(fs) == 1 and fs[0].symbol.endswith(
            "_serve_conn.writer"), [str(f) for f in fs]

    def test_asynclint_guards_the_finally_wait_closed_fix(self):
        """The forced fix, edge 2: _serve_conn's finally settles the
        writer for every normal and exception edge of the connection
        loop.  Dropping the wait_closed there re-fires the rule on
        the fall-through path."""
        src = open(os.path.join(REPO_ROOT, HTTP_FRONTEND)).read()
        fix = ("            writer.close()\n"
               "            try:\n"
               "                await writer.wait_closed()\n"
               "            except OSError:\n"
               "                pass")
        assert src.count(fix) == 1
        broken = src.replace(fix, "            writer.close()", 1)
        fs = [f for f in asynclint.lint_source(broken, HTTP_FRONTEND)
              if f.rule == "async-writer-lifecycle"]
        assert len(fs) == 1 and fs[0].symbol.endswith(
            "_serve_conn.writer"), [str(f) for f in fs]

    def test_changed_only_trigger_gating(self, monkeypatch):
        """--changed-only: asynclint re-analyzes only when serving/,
        obs/, or tools/analysis/ change; any other change set skips
        the pass entirely."""
        assert asynclint.triggered(None)
        assert asynclint.triggered({HTTP_FRONTEND})
        assert asynclint.triggered({"mxnet_tpu/obs/trace.py"})
        assert asynclint.triggered({"tools/analysis/asynclint.py"})
        assert not asynclint.triggered({"README.md",
                                        "mxnet_tpu/models/gpt.py"})

        def boom(*a, **kw):
            raise AssertionError("analyzed despite no trigger")

        monkeypatch.setattr(asynclint, "analyze", boom)
        assert asynclint.run(REPO_ROOT, only={"README.md"}) == []


class TestAsyncFixtures:
    """Every asynclint rule fires exactly once as seeded in
    fixtures/mxlint/async_fixture.py, pragma twins stay suppressed,
    the blessed clean shapes (executor hop, threadsafe reference
    bridge, awaited/cancelled/escaping tasks, try/finally writer
    settle) stay silent, and the baseline suppresses by key."""

    CLEAN = ("clean_executor_hop", "_pull", "clean_boundary_bridge",
             "clean_task_awaited", "clean_task_cancelled",
             "clean_task_escapes", "clean_writer_settled",
             "clean_lock_released_before_await")

    @pytest.fixture(scope="class")
    def findings(self):
        src = open(os.path.join(FIXTURES, "async_fixture.py")).read()
        return asynclint.lint_source(src, "async_fixture.py")

    def test_each_rule_fires_exactly_once(self, findings):
        assert _rules(findings) == {
            "async-blocking-call": 1,        # time.sleep in a coro
            "async-unawaited-coroutine": 1,  # dropped coroutine call
            "async-task-exception": 1,       # never-settled task
            "async-threadsafe-boundary": 1,  # engine-thread put_nowait
            "async-writer-lifecycle": 1,     # close() w/o wait_closed
            "async-lock-across-await": 1,    # threading lock + await
        }, [str(f) for f in findings]

    def test_findings_name_their_sites(self, findings):
        by_rule = {f.rule: f for f in findings}
        assert by_rule["async-blocking-call"].symbol == \
            "FixAsync.plant_blocking"
        assert "time.sleep" in by_rule["async-blocking-call"].message
        assert by_rule["async-unawaited-coroutine"].symbol == \
            "FixAsync.plant_unawaited"
        assert by_rule["async-task-exception"].symbol == \
            "FixAsync.plant_task.t"
        assert by_rule["async-threadsafe-boundary"].symbol == \
            "FixAsync.plant_boundary.feed"
        assert "call_soon_threadsafe" in \
            by_rule["async-threadsafe-boundary"].message
        assert by_rule["async-writer-lifecycle"].symbol == \
            "FixAsync.plant_writer.writer"
        assert "wait_closed" in \
            by_rule["async-writer-lifecycle"].message
        assert by_rule["async-lock-across-await"].symbol == \
            "FixAsync.plant_lock"

    def test_pragma_suppressed_twins(self, findings):
        src = open(os.path.join(FIXTURES, "async_fixture.py")).read()
        lines = {(f.rule, f.line) for f in findings}
        hit = 0
        for i, text in enumerate(src.splitlines(), 1):
            if "suppressed twin" in text:
                hit += 1
                assert not any(ln in (i, i + 1, i + 2, i + 3)
                               for _, ln in lines), \
                    "twin at line %d surfaced" % i
        assert hit >= 6                   # one twin per rule

    def test_clean_shapes_silent(self, findings):
        import ast
        src = open(os.path.join(FIXTURES, "async_fixture.py")).read()
        spans = {}
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                spans[node.name] = (node.lineno, node.end_lineno)
        for name in self.CLEAN:
            assert name in spans, "fixture lost clean shape %s" % name
        for f in findings:
            for name in self.CLEAN:
                lo, hi = spans[name]
                assert not (lo <= f.line <= hi), \
                    "%s seeded clean but got %s" % (name, f)

    def test_baseline_suppresses(self, findings):
        baseline = {f.key for f in findings
                    if f.rule == "async-blocking-call"}
        new, old = split_new(findings, baseline)
        assert _rules(old) == {"async-blocking-call": 1}
        assert "async-blocking-call" not in _rules(new)


# ---------------------------------------------------------------------------
# envlint (ISSUE 19 satellite): env-var documentation drift
# ---------------------------------------------------------------------------
class TestEnvlint:
    def test_every_env_read_documented(self):
        """Every literal MXNET_* key read anywhere in mxnet_tpu/ has
        a row in docs/env_vars.md — zero drift, nothing baselined."""
        fs = envlint.run(REPO_ROOT)
        assert fs == [], "\n".join(str(f) for f in fs)

    def test_doc_key_parse_sees_the_table(self):
        doc = open(os.path.join(REPO_ROOT, envlint.DOC)).read()
        keys = envlint.documented_keys(doc)
        # spot-check rows from four different table sections
        for k in ("MXNET_EAGER_JIT", "MXNET_SERVE_TIER_BYTES",
                  "MXNET_SERVE_FLIGHT_SLOTS", "MXNET_TEST_SEED"):
            assert k in keys, k

    def test_planted_undocumented_read_fires(self):
        """The drift proof: an env read with no doc row fires
        env-doc-drift once, at the read site, naming the key — for
        every read shape the scanner models."""
        doc = envlint.documented_keys(
            open(os.path.join(REPO_ROOT, envlint.DOC)).read())
        shapes = [
            'import os\nV = os.environ.get("MXNET_NEW_KNOB", "0")\n',
            'import os\nV = os.environ["MXNET_NEW_KNOB"]\n',
            'import os\nV = "MXNET_NEW_KNOB" in os.environ\n',
            'from mxnet_tpu.base import env_int\n'
            'V = env_int("MXNET_NEW_KNOB", 3)\n',
        ]
        for src in shapes:
            fs = envlint.lint_source(src, "mxnet_tpu/serving/x.py",
                                     doc)
            assert _rules(fs) == {"env-doc-drift": 1}, (src, fs)
            assert fs[0].symbol == "MXNET_NEW_KNOB"
        # ...and a documented read of the same shape stays silent
        ok = envlint.lint_source(
            'import os\nV = os.environ.get("MXNET_NEW_KNOB")\n',
            "mxnet_tpu/serving/x.py", doc | {"MXNET_NEW_KNOB"})
        assert ok == []

    def test_pragma_suppresses_intended_undocumented(self):
        fs = envlint.lint_source(
            "import os\n"
            "# mxlint: allow(env-doc-drift) -- internal-only knob\n"
            'V = os.environ.get("MXNET_SECRET_KNOB")\n',
            "mxnet_tpu/serving/x.py", set())
        assert fs == []

    def test_changed_only_trigger_gating(self, monkeypatch):
        assert envlint.triggered(None)
        assert envlint.triggered({"mxnet_tpu/base.py"})
        assert envlint.triggered({"docs/env_vars.md"})
        assert envlint.triggered({"tools/analysis/envlint.py"})
        assert not envlint.triggered({"README.md", "docs/perf.md"})

        def boom(*a, **kw):
            raise AssertionError("analyzed despite no trigger")

        monkeypatch.setattr(envlint, "analyze", boom)
        assert envlint.run(REPO_ROOT, only={"README.md"}) == []


# ---------------------------------------------------------------------------
# graphlint (ISSUE 8): live repo, fixtures, manifest + audit workflow
# ---------------------------------------------------------------------------
class TestGraphlintLiveRepo:
    def test_graphlint_zero_findings_even_baselined(self):
        """Acceptance criterion: the compiled-program audit reports
        ZERO findings with an EMPTY baseline — donation verified,
        budgets met, no undeclared f32 upcasts, no host callbacks."""
        fs = graphlint.run(REPO_ROOT)
        assert fs == [], "\n".join(str(f) for f in fs)

    def test_budget_manifest_covers_required_programs(self):
        """The committed hbm_budgets.json covers the serving step (all
        three kernels/meshes), GPT generate, and the train steps
        (acceptance criterion), agrees exactly with the registry, and
        records a trace closure for every program (the --changed-only
        scope)."""
        budgets = graphlint.load_budgets()
        progs = set(budgets["programs"])
        assert {"serving_step", "serving_step_pallas",
                "serving_step_tp", "cow_page_copy", "gpt_generate",
                "gpt_spec_block", "transformer_train_step",
                "gpt_train_step", "paged_attention_kernel",
                "tier_page_restore"} <= progs
        assert progs == {sp.name for sp in graphlint.live_programs()}
        for name, e in budgets["programs"].items():
            assert e["budget_bytes"] >= e["peak_bytes"], name
            assert e["closure"], name
        ss = budgets["programs"]["serving_step"]["closure"]
        assert "mxnet_tpu/serving/engine.py" in ss
        assert "mxnet_tpu/models/gpt.py" in ss

    def test_per_device_expected_peaks_recorded(self):
        """Round-14 acceptance: the serving step entries carry
        per-device (÷tp) expected peaks — the sharded inputs (pools +
        tp-sharded params) divide by tp, replicated inputs and the
        (conservatively replicated) intermediates do not, so the
        per-device number sits strictly between peak/tp and peak and
        decreases with tp."""
        budgets = graphlint.load_budgets()
        # the pallas step is tp=1-only this round — no ÷tp row for an
        # unreachable configuration
        assert "per_device_expected_peak_bytes" not in \
            budgets["programs"]["serving_step_pallas"]
        for name in ("serving_step", "serving_step_tp"):
            e = budgets["programs"][name]
            pd = e["per_device_expected_peak_bytes"]
            assert set(pd) == {"tp%d" % t
                               for t in graphlint._PER_DEVICE_TPS}
            peak = e["peak_bytes"]
            assert peak / 4 < pd["tp4"] < pd["tp2"] < peak, (name, pd)
        # and it regenerates identically from the live spec table
        sp = {s.name: s for s in graphlint.live_programs()}[
            "serving_step"]
        assert graphlint._per_device_expected_peaks(
            sp, budgets["programs"]["serving_step"]["peak_bytes"]) \
            == budgets["programs"]["serving_step"][
                "per_device_expected_peak_bytes"]

    def test_sharding_audit_checked_in_and_current(self):
        """The ServingEngine step-program sharding audit is committed
        (acceptance criterion) and regenerates identically.  Round 14:
        the table now verifies the ENGINE'S DECLARED shardings
        (serving/engine.py step_input_specs) against the megatron
        rules — UNCOVERED count must be 0 and nothing may mismatch."""
        path = os.path.join(REPO_ROOT, graphlint.AUDIT_PATH)
        committed = open(path).read()
        assert committed == graphlint.sharding_audit_md(REPO_ROOT)
        assert "pools[*]['kv']" in committed
        assert "UNCOVERED count: 0, mismatched: 0" in committed
        assert "P(None, None, 'tp', None)" in committed   # heads axis
        assert "covered: P(None, 'tp')" in committed      # megatron
        assert "MISMATCH — " not in committed

    def test_sharding_readiness_verifies_engine_declaration(
            self, monkeypatch):
        """The graph-sharding-readiness rule genuinely audits the LIVE
        declaration: a drifted step_input_specs — pools sharded on the
        wrong axis, a host row vector suddenly tp-sharded — fires, and
        the live declaration is clean."""
        from jax.sharding import PartitionSpec as P
        from mxnet_tpu.serving import engine as E
        assert graphlint.sharding_readiness_findings(REPO_ROOT) == []
        real = E.step_input_specs

        def drifted(params, cfg, kv_int8, tp="tp"):
            specs = list(real(params, cfg, kv_int8, tp=tp))
            # pools sharded on the PAGE axis instead of heads, and the
            # token rows tp-sharded (two distinct mismatch classes)
            specs[1] = [{"kv": P(None, tp, None, None),
                         "s": P(None, tp, None, None)}
                        for _ in range(cfg.n_layers)]
            specs[2] = P(tp)
            return tuple(specs)

        monkeypatch.setattr(E, "step_input_specs", drifted)
        fs = graphlint.sharding_readiness_findings(REPO_ROOT)
        assert _rules(fs) == {"graph-sharding-readiness": 1}
        assert "mismatch" in fs[0].symbol
        # anchored at the declaration, not at graphlint
        assert fs[0].path == "mxnet_tpu/serving/engine.py"

    def test_graphlint_guards_the_kv_quantize_fix(self, monkeypatch):
        """Reverting _kv_quantize to the round-4 bf16-accumulation
        version (bf16 max/divide, cosmetic f32 upcast of the stacked
        scales) re-fires graph-dtype-drift on the serving step — the
        pass genuinely guards the fix shipped in this PR (PR-4/7
        convention)."""
        import jax.numpy as jnp
        from mxnet_tpu.models import gpt as G
        src = open(os.path.join(REPO_ROOT,
                                "mxnet_tpu/models/gpt.py")).read()
        assert "kf = k.astype(jnp.float32)" in src   # the fix is live

        def old_kv_quantize(k, v):
            sk = jnp.maximum(jnp.max(jnp.abs(k), axis=-1) / 127.0,
                             1e-8)
            sv = jnp.maximum(jnp.max(jnp.abs(v), axis=-1) / 127.0,
                             1e-8)
            kq = jnp.clip(jnp.round(k / sk[..., None]), -127, 127
                          ).astype(jnp.int8)
            vq = jnp.clip(jnp.round(v / sv[..., None]), -127, 127
                          ).astype(jnp.int8)
            return (jnp.concatenate([kq, vq], axis=-1),
                    jnp.stack([sk, sv], axis=-1).astype(jnp.float32))

        monkeypatch.setattr(G, "_kv_quantize", old_kv_quantize)
        # pjit caches the traced jaxpr per (fn, avals) — drop it so
        # the re-trace actually sees the monkeypatched quantizer, and
        # drop it AGAIN on the way out so later tests re-tracing the
        # _step_cache'd fn do not read the poisoned bf16 jaxpr back
        import jax
        from mxnet_tpu.serving import engine as E
        jax.clear_caches()
        try:
            sp = {s.name: s for s in graphlint.live_programs()}[
                "serving_step"]
            fs = graphlint.check_program(
                sp, REPO_ROOT, budgets=graphlint.load_budgets())
        finally:
            E._step_cache.clear()
            jax.clear_caches()
        assert _rules(fs)["graph-dtype-drift"] >= 1, \
            [str(f) for f in fs]

    def test_dropping_donation_refires(self, monkeypatch):
        """Rebuilding the serving step with donate_argnums stripped
        (what a careless _make_step refactor would do) fires
        graph-donation — the registry audits the LIVE builder."""
        import jax
        from mxnet_tpu.serving import engine as E
        real_jit = jax.jit

        def nodonate_jit(*a, **kw):
            kw.pop("donate_argnums", None)
            return real_jit(*a, **kw)

        monkeypatch.setattr(jax, "jit", nodonate_jit)
        E._step_cache.clear()
        try:
            sp = {s.name: s for s in graphlint.live_programs()}[
                "serving_step"]
            fs = graphlint.check_program(
                sp, REPO_ROOT, budgets=graphlint.load_budgets())
        finally:
            E._step_cache.clear()    # never leak the undonated step
        assert _rules(fs)["graph-donation"] == 1, [str(f) for f in fs]

    def test_dropping_donation_refires_under_shardings(self,
                                                       monkeypatch):
        """Round-14 acceptance: pool donation is verified on the
        SHARDED step too — stripping donate_argnums from the
        tp-lowered build (in/out shardings intact) fires
        graph-donation, i.e. the gate did not silently stop applying
        when the program gained a mesh."""
        import jax
        from mxnet_tpu.serving import engine as E
        real_jit = jax.jit

        def nodonate_jit(*a, **kw):
            kw.pop("donate_argnums", None)
            return real_jit(*a, **kw)

        monkeypatch.setattr(jax, "jit", nodonate_jit)
        E._step_cache.clear()
        try:
            sp = {s.name: s for s in graphlint.live_programs()}[
                "serving_step_tp"]
            fs = graphlint.check_program(
                sp, REPO_ROOT, budgets=graphlint.load_budgets())
        finally:
            E._step_cache.clear()
        assert _rules(fs)["graph-donation"] == 1, [str(f) for f in fs]

    def test_changed_only_traces_by_closure(self, monkeypatch):
        """--changed-only re-traces a program iff a file in its
        recorded trace closure changed (analysis-infra changes always
        re-trace; --all / tier-1 ignores the scope entirely)."""
        budgets = graphlint.load_budgets()
        sp = {s.name: s for s in graphlint.live_programs()}[
            "serving_step"]
        assert graphlint._needs_trace(
            sp, budgets, {"mxnet_tpu/serving/engine.py"})
        assert graphlint._needs_trace(
            sp, budgets, {"tools/analysis/graphlint.py"})
        assert not graphlint._needs_trace(sp, budgets, {"README.md"})

        # nothing changed -> NO program traced at all
        def no_trace(*a, **kw):
            raise AssertionError("traced despite empty change set")

        monkeypatch.setattr(graphlint, "check_program", no_trace)
        assert graphlint.run(REPO_ROOT, only=set()) == []

    def test_update_budgets_never_relaxes(self, tmp_path):
        """--update-budgets re-records peak_bytes and closures but a
        committed budget only ever ratchets DOWN (perf-gate
        semantics); a program over its budget stays a finding until
        the budget is hand-edited with justification."""
        gf = _load_graph_fixture()
        sp = {s.name: s for s in gf.PROGRAMS}["fix_over_budget"]
        p = tmp_path / "budgets.json"
        p.write_text(json.dumps({"version": 1, "programs": {
            "fix_over_budget": {"peak_bytes": 5, "budget_bytes": 5,
                                "closure": []}}}))
        data = graphlint.update_budgets(REPO_ROOT, path=str(p),
                                        specs=[sp])
        e = data["programs"]["fix_over_budget"]
        assert e["peak_bytes"] > 5          # measurement re-recorded
        assert e["budget_bytes"] == 5       # budget NOT relaxed
        # ...and a generous budget tightens to ceil(peak * HEADROOM)
        p.write_text(json.dumps({"version": 1, "programs": {
            "fix_over_budget": {"peak_bytes": 10 ** 9,
                                "budget_bytes": 10 ** 9,
                                "closure": []}}}))
        data = graphlint.update_budgets(REPO_ROOT, path=str(p),
                                        specs=[sp])
        e = data["programs"]["fix_over_budget"]
        import math
        assert e["budget_bytes"] == int(math.ceil(
            e["peak_bytes"] * graphlint.HEADROOM))

    def test_estimator_is_deterministic_and_scales(self):
        """peak_live_bytes: bit-stable across runs, and a program that
        materializes an extra full-size temporary estimates strictly
        higher (the property the budget gate rides on)."""
        import jax
        import jax.numpy as jnp
        s = jax.ShapeDtypeStruct((64, 64), jnp.float32)

        def lean(x):
            return (x * 2.0).sum()

        def fat(x):
            a = x * 2.0
            b = x * 3.0
            c = x * 4.0
            return (a + b + c).sum()

        j1 = jax.make_jaxpr(lean)(s)
        p1 = graphlint.peak_live_bytes(j1)
        assert p1 == graphlint.peak_live_bytes(jax.make_jaxpr(lean)(s))
        assert graphlint.peak_live_bytes(jax.make_jaxpr(fat)(s)) > p1


class TestGraphFixtures:
    """Every graphlint rule fires exactly once over the seeded toy
    registry in fixtures/mxlint/graph_fixture.py, pragma twins stay
    suppressed, clean programs stay silent, and the baseline
    suppresses by key (ISSUE 8 satellite)."""

    @pytest.fixture(scope="class")
    def fixture(self):
        return _load_graph_fixture()

    @pytest.fixture(scope="class")
    def findings(self, fixture):
        return graphlint.run(REPO_ROOT, specs=fixture.PROGRAMS,
                             budgets=fixture.BUDGETS)

    def test_each_rule_fires_exactly_once(self, findings):
        assert _rules(findings) == {
            "graph-donation": 1,      # fix_dropped_donation
            "graph-dtype-drift": 1,   # fix_f32_upcast
            "graph-hbm-budget": 1,    # fix_over_budget
            "graph-host-sync": 1,     # fix_host_callback
        }, [str(f) for f in findings]

    def test_findings_name_their_programs(self, findings):
        by_rule = {f.rule: f for f in findings}
        assert "fix_dropped_donation" in \
            by_rule["graph-donation"].symbol
        assert "fix_f32_upcast" in by_rule["graph-dtype-drift"].symbol
        assert by_rule["graph-hbm-budget"].symbol == "fix_over_budget"
        assert "debug_print" in by_rule["graph-host-sync"].symbol

    def test_dtype_finding_anchors_at_the_upcast_line(self, findings):
        f = [x for x in findings if x.rule == "graph-dtype-drift"][0]
        src = open(os.path.join(FIXTURES,
                                "graph_fixture.py")).read()
        line = src.splitlines()[f.line - 1]
        assert "astype(jnp.float32)" in line

    def test_pragma_suppressed_twins(self, findings):
        for f in findings:
            assert "twin" not in f.symbol, str(f)

    def test_clean_programs_silent(self, findings):
        for f in findings:
            assert "fine_" not in f.symbol, str(f)

    def test_baseline_suppresses(self, findings):
        baseline = {f.key for f in findings
                    if f.rule == "graph-donation"}
        new, old = split_new(findings, baseline)
        assert _rules(old) == {"graph-donation": 1}
        assert "graph-donation" not in _rules(new)

    def test_missing_budget_entry_is_a_finding(self, fixture):
        sp = {s.name: s for s in fixture.PROGRAMS}["fix_over_budget"]
        fs = graphlint.check_program(sp, REPO_ROOT,
                                     budgets={"programs": {}})
        assert _rules(fs)["graph-hbm-budget"] == 1
        assert "--update-budgets" in fs[0].message

    def test_growth_over_manifest_is_a_finding(self, fixture):
        """Within budget but >10% over the recorded peak still fires
        (the trajectory half of the gate)."""
        sp = {s.name: s for s in fixture.PROGRAMS}["fix_over_budget"]
        fs = graphlint.check_program(
            sp, REPO_ROOT,
            budgets={"programs": {"fix_over_budget": {
                "peak_bytes": 100, "budget_bytes": 10 ** 9}}})
        assert _rules(fs) == {"graph-hbm-budget": 1}
        assert "grew" in fs[0].message


# ---------------------------------------------------------------------------
# 3. infra behaviors
# ---------------------------------------------------------------------------
class TestInfra:
    def test_pragma_comment_block_above(self):
        src = ("x = 1\n"
               "# mxlint: allow(host-sync) -- reason\n"
               "# second comment line\n"
               "y = np.asarray(out)\n")
        f = Finding("jax", "host-sync", "m.py", 4, "np.asarray", "m")
        assert apply_pragmas([f], src) == []

    def test_pragma_wrong_rule_does_not_suppress(self):
        src = "# mxlint: allow(retrace)\ny = np.asarray(out)\n"
        f = Finding("jax", "host-sync", "m.py", 2, "np.asarray", "m")
        assert apply_pragmas([f], src) == [f]

    def test_baseline_roundtrip(self, tmp_path):
        p = tmp_path / "baseline.json"
        p.write_text('{"version": 1, "allow": [{"rule": "r", '
                     '"path": "p.py", "symbol": "s"}, "a:b:c"]}')
        keys = load_baseline(str(p))
        assert keys == {"r:p.py:s", "a:b:c"}

    def test_checked_in_baseline_is_empty(self):
        """The suite ships with zero accepted debt — anything new must
        be fixed or explicitly pragma'd with a justification."""
        keys = load_baseline(os.path.join(
            REPO_ROOT, "tools", "analysis", "baseline.json"))
        assert keys == set()

    def test_findings_json_schema(self):
        """--format json (ISSUE 8 satellite): the stable CI schema —
        every finding carries rule/file/line/message/fingerprint, the
        fingerprint is the sha1 of the line-independent baseline key
        (stable under unrelated edits), statuses partition
        new/baselined."""
        f1 = Finding("jax", "host-sync", "m.py", 7, "np.asarray", "m1")
        f2 = Finding("jax", "host-sync", "m.py", 9, "np.asarray", "m2")
        old = Finding("abi", "abi-argtypes", "n.py", 0, "MXFoo", "m3")
        data = findings_json({"new": [f1], "baselined": [old]})
        assert data["version"] == 1
        assert data["new"] == 1 and data["baselined"] == 1
        entry = data["findings"][0]
        assert set(entry) == {"rule", "file", "line", "message",
                              "fingerprint", "analyzer", "symbol",
                              "status"}
        assert entry == {"rule": "host-sync", "file": "m.py",
                         "line": 7, "message": "m1",
                         "analyzer": "jax", "symbol": "np.asarray",
                         "status": "new",
                         "fingerprint": entry["fingerprint"]}
        # line-independent: same key -> same fingerprint; 12 hex chars
        fp1 = findings_json({"new": [f1], "baselined": []})
        fp2 = findings_json({"new": [f2], "baselined": []})
        assert fp1["findings"][0]["fingerprint"] == \
            fp2["findings"][0]["fingerprint"]
        assert len(entry["fingerprint"]) == 12
        int(entry["fingerprint"], 16)
        assert data["findings"][1]["status"] == "baselined"

    def test_cli_format_json_round_trips(self, capsys):
        """`python -m tools.analysis --format json` emits parseable
        JSON with zero new findings on the live repo (what
        tools/run_static_analysis.sh passes through for CI)."""
        from tools.analysis import runner
        rc = runner.main(["--format", "json", "--changed-only"])
        out = capsys.readouterr().out
        data = json.loads(out)
        assert rc == 0
        assert data["version"] == 1
        assert data["new"] == 0
