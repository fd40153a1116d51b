"""JAX hazard linter (mxlint analyzer 2 of 3) — Python ``ast`` based.

Rules
-----
``host-sync``  In a designated hot-loop region, a device→host
    materialization of a value produced by a compiled step function:
    ``np.asarray``/``np.array`` on a *device-tainted* expression,
    ``.item()`` / ``.tolist()`` / ``.block_until_ready()`` on one,
    ``float()``/``int()``/``bool()`` of one, or ``jax.device_get`` of
    one.  Taint is a simple intra-region dataflow: results of calls to
    compiled-step callables (terminal name matching ``*step_fn``, a
    name bound from ``jax.jit(...)``, or a function defined under
    ``@jax.jit``) are tainted; round 21 adds two sources for the
    engine's dispatch / drain split — calls to ``*_dispatch`` (the helper
    returns the step program's output un-materialized) and the
    ``DEVICE_PARAMS`` registry (a hot-region function that RECEIVES a
    step result as a parameter, like the engine's ``_drain``, declares
    it there).  Taint propagates through subscripts, attributes,
    arithmetic, and tuple unpacking; a flagged materialization (e.g.
    ``x = np.asarray(x)``) clears it — the sync happened there,
    downstream host math is free.  ``jnp.asarray`` (host→device) is
    deliberately NOT a sync.

``retrace``  Retrace/recompile churn: (a) ``jax.jit(...)`` called
    inside a ``for``/``while`` body — the compile cache is keyed on
    the function object, so a fresh closure per iteration recompiles
    every time (the repo idiom is a module-level keyed cache, see
    ``models/gpt.py``); (b) a known-jitted callable invoked with a
    bare Python numeric literal or a ``list``/``dict``/``set`` display
    as an argument — scalars belong in the cache key / static args,
    not the traced signature.

``clock-mix``  In modules on the profiler's shared trace clock
    (``time.perf_counter`` — obs/, serving/, profiler, serve_bench),
    a call to ``time.time``/``time.monotonic``/``time.clock`` or
    ``datetime.*.now`` — mixing clocks skews every span it touches.

``bench-no-sync``  (round 12) In benchmark modules, a timed region —
    opened by ``t0 = time.perf_counter()``, closed by any other
    ``perf_counter()`` read — containing a call to a recognized
    jitted/step callable whose result is never synced
    (``block_until_ready`` / ``jax.device_get`` / ``np.asarray`` /
    ``float()``/``.item()``) before the closing read.  That clock
    measures DISPATCH, not execution — the hazard class that bit
    ``serve_bench._fixed_batch`` in round 9.  Dispatch-timing on
    purpose?  Pragma it with the justification.

Suppression: ``# mxlint: allow(<rule>)`` on the line or the comment
block directly above (see ``findings.py``).
"""
from __future__ import annotations

import ast
import fnmatch
import os
import re
from typing import List, Optional, Set, Tuple

from .findings import Finding, apply_pragmas

__all__ = ["HOT_REGIONS", "CLOCK_MODULES", "lint_source", "run"]

# (repo-relative glob, qualname regex) — the designated hot-loop regions
HOT_REGIONS: List[Tuple[str, str]] = [
    # round 11: the speculation plan/draft path runs once per engine
    # step on the host — it must stay pure host work (no device syncs
    # beyond step()'s one pragma'd token read-back).
    # round 21: the step's phases — plan build, dispatch, deferred
    # drain/commit — all run once per step on the caller's thread; a
    # stray sync in any of them un-hides exactly the host latency the
    # pipelined loop exists to hide
    ("mxnet_tpu/serving/engine.py",
     r"(?:.*\.)?(step|_drain|_build_plan|_dispatch|_commit"
     r"|_plan_speculation)$"),
    # round 10: the cluster router loop (per-replica worker + routing
    # + completion) and the prefix-cache match/insert/evict paths run
    # once per step / per admission — no host syncs may sneak in.
    # round 12 widens both: the watchdog/failover path (a host sync
    # inside _fail_replica stalls EVERY waiter under the cluster lock)
    # and the eviction/COW leaf (_drop runs inside the allocator's
    # pressure callback, mid-admission)
    # round 17: the round-16 autoscaler actuation paths protolint's
    # call-graph walks also cover — add_worker/drain_worker and the
    # late-join handshake helper run while the cluster serves; a host
    # sync or in-loop jit there stalls scale actuation behind device
    # work exactly like a stall in the failover path would
    ("mxnet_tpu/serving/cluster.py",
     r"(?:.*\.)?(_worker|_pump_inbox|_complete|_route_locked"
     r"|_monitor_loop|_fail_replica|drain_replica"
     r"|add_worker|drain_worker|_handshake_one)$"),
    ("mxnet_tpu/serving/prefix_cache.py",
     r"(?:.*\.)?(match|insert_chain|evict|_drop)$"),
    # round 15: the disaggregated page export/install paths run per
    # transfer on the worker main loop — the ONE device round-trip
    # each (gather→host, host→scatter) is the transfer itself; any
    # additional sync, in-loop jit, or clock mix here stalls the
    # prefill→decode pipeline per page frame
    ("mxnet_tpu/serving/paged_kv.py",
     r"(?:.*\.)?(export_pages|install_pages)$"),
    ("mxnet_tpu/serving/page_streamer.py", r".*"),
    # round 18: the KV-tiering hot paths — spill runs inside the
    # allocator's pressure callback (mid-admission, mid-step phase A),
    # warm restore + swap-in run inside match()/_admit on the serving
    # thread; the ONE device round-trip each (export gather / install
    # scatter) IS the tier transfer — any additional sync, in-loop
    # jit, or clock mix here prices every pressure event and every
    # preemption resume
    ("mxnet_tpu/serving/tier_store.py", r".*"),
    ("mxnet_tpu/serving/prefix_cache.py",
     r"(?:.*\.)?(_spill_entry|_restore_run|_spilled_run|spill"
     r"|probe_depth|spilled_content)$"),
    ("mxnet_tpu/serving/engine.py",
     r"(?:.*\.)?(_preempt_victim|_swap_in)$"),
    # round 12: the metrics-registry mutation path — instrument
    # creation and reset run under the registry lock; a device sync or
    # in-loop jit there blocks every scrape and engine step behind it
    ("mxnet_tpu/obs/metrics.py",
     r"(?:.*\.)?(_get|counter|gauge|histogram|reset|reset_values)$"),
    # round 11: the host-side drafters feed the step builder — same
    # once-per-step budget as the engine scheduler
    ("mxnet_tpu/serving/drafters.py", r".*"),
    # round 11: the paged-attention kernel call path (builder + entry
    # point) is traced inside the step program — a stray host sync or
    # an in-loop jit here retraces/stalls every serving step
    ("mxnet_tpu/kernels/paged_attention.py", r".*"),
    ("mxnet_tpu/models/gpt.py", r"generate(?:_speculative)?$"),
    ("benchmark/serve_bench.py", r".*"),
    ("benchmark/spec_decode_probe.py", r".*"),
    # round 16: the autoscaler control loop ticks continuously next
    # to the serving threads (a host sync or in-loop jit there stalls
    # every scaling decision behind device work), the chaos driver's
    # poll/apply path runs inside the replay's timed loop, and the
    # trace generator feeds seeded workloads whose timing sections
    # must stay pure host work (bench-no-sync applies to the
    # benchmark/ module as usual)
    ("mxnet_tpu/serving/autoscaler.py", r".*"),
    ("mxnet_tpu/serving/chaos.py", r".*"),
    ("benchmark/traffic_trace.py", r".*"),
    # round 19: the training scale-out hot paths — the ICI-allreduce
    # KVStore's push/bucketing runs once per gradient sync (an in-loop
    # jit or stray host sync there serializes every training step
    # behind the collective), and the FSDP rule-table/composition
    # helpers are traced inside the sharded train step
    ("mxnet_tpu/kvstore/ici.py", r".*"),
    ("mxnet_tpu/parallel/fsdp.py", r".*"),
    # round 20: the HTTP front door's streaming/cancel paths run on
    # the asyncio event loop thread right next to the serving threads
    # — ONE loop serves every open connection, so a device sync, an
    # in-loop jit, or a clock mix in the SSE pump or the disconnect→
    # cancel path stalls every stream at once (the per-request
    # cluster work rides the executor, never the loop)
    ("mxnet_tpu/serving/http_frontend.py",
     r"(?:.*\.)?(_stream_sse|_respond_json|_run_request"
     r"|_cancel_disconnected|_serve_conn|_conn_loop"
     r"|_handle_generate|_handle_statusz|_handle_trace)$"),
    ("benchmark/http_bench.py", r".*"),
    # round 22: the zero-copy put transport and its cluster data-plane
    # callers run per page frame between the prefill and decode engine
    # loops — segment write/mmap-read and the caps/put framing must
    # stay pure host work (the device hand-off is the install scatter,
    # already covered via paged_kv.install_pages), and the peer-fetch
    # / stream / fetch-serve methods that choose the transport sit on
    # the worker main loop where a stray sync stalls decode admission
    ("mxnet_tpu/serving/transport.py", r".*"),
    ("mxnet_tpu/serving/cluster.py",
     r"(?:.*\.)?(_send_pages_frame|_serve_fetches|_stream_pages"
     r"|_fetch_remote|_peer_handler|_peer_conn)$"),
    # round 23: the flight recorder's emit path runs at every wire
    # send/recv, page install, and step boundary in BOTH router and
    # worker processes, and the span-ship/merge paths ride the worker
    # stats tick and the router recv loop — a device sync, in-loop
    # jit, or clock mix in any of them prices every hot-path event
    # (the recorder's mmap store must stay pure host work)
    ("mxnet_tpu/obs/flight.py", r".*"),
    ("mxnet_tpu/obs/trace.py", r".*"),
    ("mxnet_tpu/serving/cluster.py",
     r"(?:.*\.)?(_on_spans|_on_clock|_clock_ping|_maybe_send_stats"
     r"|_commit_tokens_locked|_slo_locked)$"),
]

# modules whose timestamps must stay on the shared perf_counter clock
CLOCK_MODULES: List[str] = [
    "mxnet_tpu/obs/*.py",
    "mxnet_tpu/serving/*.py",
    "mxnet_tpu/profiler.py",
    "benchmark/serve_bench.py",
    "benchmark/http_bench.py",
]

# modules whose perf_counter regions must sync their jitted work
# (bench-no-sync — every benchmark driver times compiled programs)
BENCH_MODULES: List[str] = [
    "benchmark/*.py",
]

STEP_FN_RE = re.compile(r".*step_fn$")
# round 21: the engine's step routes the raw step-program output
# through ``_dispatch`` (it stages inputs and returns the jitted call's
# result WITHOUT materializing) — in hot regions a call to it is a
# device result exactly like a *step_fn call.  Kept separate from
# STEP_FN_RE so the bench linter's jit-call heuristic is unchanged.
DEVICE_OUT_RE = re.compile(r".*(?:step_fn|_dispatch)$")
# hot-region functions that RECEIVE a step-program result as a
# parameter (``_drain`` gets step N's sampled tokens, at pipeline
# depth 1 while step N+1 executes): (repo-relative glob, qualname regex, params) —
# the named parameters are seeded device-tainted before linting
DEVICE_PARAMS: List[Tuple[str, str, Tuple[str, ...]]] = [
    ("mxnet_tpu/serving/engine.py", r"(?:.*\.)?_drain$", ("tok",)),
]
_NP_ALIASES = {"np", "numpy", "onp"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_WRONG_CLOCKS = {("time", "time"), ("time", "monotonic"),
                 ("time", "clock")}


def _terminal(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _dotted(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_jax_jit(call: ast.Call) -> bool:
    return _dotted(call.func) in ("jax.jit", "jit")


class _RegionLinter(ast.NodeVisitor):
    """Lints one hot region (a function def and everything nested)."""

    def __init__(self, path: str, findings: List[Finding]):
        self.path = path
        self.findings = findings
        self.tainted: Set[str] = set()
        self.jitted: Set[str] = set()
        self.loop_depth = 0

    # -- helpers ------------------------------------------------------
    def _add(self, rule: str, node: ast.AST, symbol: str, msg: str):
        self.findings.append(Finding(
            "jax", rule, self.path, node.lineno, symbol, msg))

    def _expr_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Call):
            t = _terminal(node.func)
            if t and (DEVICE_OUT_RE.match(t) or t in self.jitted):
                return True
            return any(self._expr_tainted(a) for a in node.args)
        for child in ast.iter_child_nodes(node):
            if self._expr_tainted(child):
                return True
        return False

    def _is_step_call(self, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        t = _terminal(node.func)
        return bool(t and (DEVICE_OUT_RE.match(t) or t in self.jitted))

    # -- taint bookkeeping --------------------------------------------
    def visit_FunctionDef(self, node):
        for dec in node.decorator_list:
            if _dotted(dec) in ("jax.jit", "jit") or (
                    isinstance(dec, ast.Call) and _is_jax_jit(dec)):
                self.jitted.add(node.name)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assign(self, node):
        self.generic_visit(node)  # flag RHS syncs before retargeting
        value_tainted = (self._is_step_call(node.value)
                         or self._expr_tainted(node.value))
        # a HOST materialization on the RHS *clears* taint: np.asarray
        # (np alias only — jnp.asarray stays on device and must keep
        # the taint), .item()/.tolist(), jax.device_get.  The sync
        # happened there; its result is host memory.
        if isinstance(node.value, ast.Call):
            func = node.value.func
            if isinstance(func, ast.Attribute):
                base = func.value
                is_np_call = (func.attr in ("asarray", "array")
                              and isinstance(base, ast.Name)
                              and base.id in _NP_ALIASES)
                # NOT block_until_ready: it returns the same device
                # array — a later float()/np.asarray is still a copy
                if is_np_call or func.attr in ("item", "tolist",
                                               "device_get"):
                    value_tainted = False
            if _is_jax_jit(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.jitted.add(tgt.id)
        names: List[str] = []
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                names.append(tgt.id)
            elif isinstance(tgt, (ast.Tuple, ast.List)):
                names.extend(e.id for e in tgt.elts
                             if isinstance(e, ast.Name))
        for name in names:
            if value_tainted:
                self.tainted.add(name)
            else:
                self.tainted.discard(name)

    # -- loops (for the jit-in-loop rule) -----------------------------
    def visit_For(self, node):
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_While = visit_For
    visit_AsyncFor = visit_For

    # -- the rules ----------------------------------------------------
    def visit_Call(self, node):
        self.generic_visit(node)
        func = node.func
        dotted = _dotted(func)

        # retrace (a): jax.jit built inside a loop
        if _is_jax_jit(node) and self.loop_depth > 0:
            self._add("retrace", node, dotted or "jax.jit",
                      "jax.jit(...) inside a loop recompiles every "
                      "iteration — build once and cache (gpt.py idiom)")

        # retrace (b): jitted callable fed literals/containers
        if self._is_step_call(node):
            for arg in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(arg, ast.Constant) and isinstance(
                        arg.value, (int, float)) and not isinstance(
                        arg.value, bool):
                    self._add("retrace", node, _terminal(func) or "?",
                              "Python scalar literal in a jitted call "
                              "signature — mark static or fold into "
                              "the cache key")
                    break
                if isinstance(arg, (ast.List, ast.Dict, ast.Set)):
                    self._add("retrace", node, _terminal(func) or "?",
                              "container display in a jitted call "
                              "signature — structure changes retrace")
                    break

        # host-sync
        if isinstance(func, ast.Attribute):
            base = func.value
            if (func.attr in ("asarray", "array")
                    and isinstance(base, ast.Name)
                    and base.id in _NP_ALIASES
                    and any(self._expr_tainted(a) for a in node.args)):
                self._add("host-sync", node, "%s.%s" % (base.id,
                                                        func.attr),
                          "implicit device sync: numpy materialization "
                          "of a step-program result in a hot loop")
            elif func.attr in _SYNC_METHODS and self._expr_tainted(base):
                self._add("host-sync", node, "." + func.attr,
                          "device sync on a step-program result in a "
                          "hot loop")
            elif dotted.endswith("device_get") and any(
                    self._expr_tainted(a) for a in node.args):
                self._add("host-sync", node, dotted,
                          "jax.device_get of a step-program result in "
                          "a hot loop")
        elif isinstance(func, ast.Name) and func.id in ("float", "int",
                                                        "bool"):
            if any(self._expr_tainted(a) for a in node.args):
                self._add("host-sync", node, func.id,
                          "%s() of a step-program result forces a "
                          "device sync in a hot loop" % func.id)


class _ClockLinter(ast.NodeVisitor):
    def __init__(self, path: str, findings: List[Finding]):
        self.path = path
        self.findings = findings

    def visit_Call(self, node):
        self.generic_visit(node)
        dotted = _dotted(node.func)
        parts = tuple(dotted.rsplit(".", 2)[-2:])
        if parts in _WRONG_CLOCKS:
            self.findings.append(Finding(
                "jax", "clock-mix", self.path, node.lineno, dotted,
                "wrong clock on a trace-clock module — use "
                "time.perf_counter (profiler.now_us) so spans "
                "interleave in one dump"))
        elif dotted.endswith(".now") and "datetime" in dotted:
            self.findings.append(Finding(
                "jax", "clock-mix", self.path, node.lineno, dotted,
                "wall-clock datetime in a trace-clock module — use "
                "time.perf_counter"))


class _BenchSyncLinter:
    """bench-no-sync: linear scan of each function for timed regions
    whose jitted work is never synced before the closing clock read.

    Recognized jitted callables: names bound from ``jax.jit(...)``
    anywhere in the module, ``@jax.jit`` defs, and ``*step_fn`` names
    (the same vocabulary as the taint linter).  Unknown callables
    (``eng.step()``, host loops) never flag — the rule is deliberately
    precise rather than complete."""

    _SYNCS = {"block_until_ready", "device_get", "item", "tolist"}

    def __init__(self, path: str, findings: List[Finding]):
        self.path = path
        self.findings = findings
        self.jitted: Set[str] = set()
        self.sync_helpers: Set[str] = set()

    def collect_jitted(self, tree: ast.Module):
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if _dotted(dec) in ("jax.jit", "jit") or (
                            isinstance(dec, ast.Call)
                            and _is_jax_jit(dec)):
                        self.jitted.add(node.name)
                        break
                else:
                    # a plain function whose body syncs (the repo's
                    # hard_sync-style helpers) is itself a sync
                    if any(isinstance(n, ast.Call) and self._is_sync(n)
                           for n in ast.walk(node)):
                        self.sync_helpers.add(node.name)
            elif isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call) and _is_jax_jit(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.jitted.add(tgt.id)

    def _is_clock(self, call: ast.Call) -> bool:
        d = _dotted(call.func)
        # any wall-clock read opens/closes a timed region — clock-mix
        # separately polices WHICH clock trace-clock modules may use
        return d.endswith("perf_counter") or d in ("time.time",
                                                   "time.monotonic")

    def _is_sync(self, call: ast.Call) -> bool:
        func = call.func
        if isinstance(func, ast.Attribute):
            if func.attr in self._SYNCS:
                return True
            if func.attr in ("asarray", "array") and isinstance(
                    func.value, ast.Name) and \
                    func.value.id in _NP_ALIASES:
                return True
        return isinstance(func, ast.Name) and (
            func.id in ("float", "int")
            or func.id in self.sync_helpers)

    def _is_jit_call(self, call: ast.Call) -> bool:
        t = _terminal(call.func)
        if not t:
            return False
        if STEP_FN_RE.match(t):
            return True
        # only BARE names match the jitted set: `eng.run()` must not
        # alias an unrelated local `@jax.jit def run` (the engine
        # drain loop syncs internally every step)
        return isinstance(call.func, ast.Name) and t in self.jitted

    def lint_function(self, fn):
        self.timing_open = False
        self.unsynced = None
        self._walk(fn.body)

    def _walk(self, stmts):
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef,
                                 ast.AsyncFunctionDef, ast.ClassDef)):
                continue
            self._stmt(stmt)
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, attr, None)
                if sub:
                    self._walk(sub)
            for h in getattr(stmt, "handlers", ()):
                self._walk(h.body)

    def _stmt(self, stmt):
        # calls of THIS statement only (compound bodies walk
        # separately), outermost-first in source order
        sub = {id(s) for attr in ("body", "orelse", "finalbody")
               for s in getattr(stmt, attr, ()) or ()}
        sub |= {id(s) for h in getattr(stmt, "handlers", ())
                for s in h.body}
        calls = [n for n in ast.walk(stmt)
                 if isinstance(n, ast.Call) and id(n) not in sub]
        calls.sort(key=lambda c: (c.lineno, c.col_offset))
        consumed: Set[int] = set()
        opener = (isinstance(stmt, ast.Assign)
                  and isinstance(stmt.value, ast.Call)
                  and self._is_clock(stmt.value))
        for call in calls:
            if id(call) in consumed:
                continue
            if self._is_clock(call):
                if self.timing_open and self.unsynced is not None:
                    # ANY later clock read closes the region — a bare
                    # `t1 = perf_counter()` assignment both closes the
                    # old region and opens the next one
                    self.findings.append(Finding(
                        "jax", "bench-no-sync", self.path,
                        call.lineno, "perf_counter",
                        "timed region closes without syncing the "
                        "jitted call at line %d — this clock measures "
                        "dispatch, not execution (block_until_ready "
                        "the result; round-9 _fixed_batch hazard)"
                        % self.unsynced))
                    self.unsynced = None
                if opener and call is stmt.value:
                    self.timing_open = True
                    self.unsynced = None
            elif self._is_sync(call):
                self.unsynced = None
                for inner in ast.walk(call):
                    if isinstance(inner, ast.Call) and inner is not \
                            call:
                        consumed.add(id(inner))
            elif self._is_jit_call(call) and self.timing_open:
                self.unsynced = call.lineno


def _qualname_functions(tree: ast.Module):
    """Yield (qualname, FunctionDef) for every function, with class
    nesting reflected (``Class.method``)."""
    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                yield prefix + child.name, child
                # nested defs are linted as part of their region root
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, prefix + child.name + ".")
    yield from walk(tree, "")


def lint_source(source: str, rel_path: str,
                region_re: Optional[str] = None,
                clock: Optional[bool] = None,
                bench: Optional[bool] = None) -> List[Finding]:
    """Lint one module.  ``region_re``/``clock``/``bench`` override
    the repo config (fixture tests drive this directly)."""
    tree = ast.parse(source, rel_path)
    findings: List[Finding] = []

    patterns = []
    if region_re is not None:
        patterns.append(re.compile(region_re))
    else:
        patterns.extend(re.compile(rx) for glob, rx in HOT_REGIONS
                        if fnmatch.fnmatch(rel_path, glob))
    if patterns:
        for qualname, fn in _qualname_functions(tree):
            if any(p.match(qualname) for p in patterns):
                linter = _RegionLinter(rel_path, findings)
                for glob, rx, pnames in DEVICE_PARAMS:
                    if fnmatch.fnmatch(rel_path, glob) and \
                            re.match(rx, qualname):
                        linter.tainted.update(pnames)
                linter.visit(fn)

    if clock is None:
        clock = any(fnmatch.fnmatch(rel_path, g) for g in CLOCK_MODULES)
    if clock:
        _ClockLinter(rel_path, findings).visit(tree)

    if bench is None:
        bench = any(fnmatch.fnmatch(rel_path, g) for g in BENCH_MODULES)
    if bench:
        linter = _BenchSyncLinter(rel_path, findings)
        linter.collect_jitted(tree)
        for _, fn in _qualname_functions(tree):
            linter.lint_function(fn)

    return apply_pragmas(findings, source)


def run(root: str, only: Optional[Set[str]] = None) -> List[Finding]:
    """Lint every configured module under ``root``.  ``only``: optional
    set of repo-relative paths (--changed-only)."""
    rels = {glob for glob, _ in HOT_REGIONS} | set(CLOCK_MODULES) \
        | set(BENCH_MODULES)
    seen: Set[str] = set()
    findings: List[Finding] = []
    for pattern in sorted(rels):
        dirname = os.path.dirname(pattern)
        full_dir = os.path.join(root, dirname)
        if not os.path.isdir(full_dir):
            continue
        for name in sorted(os.listdir(full_dir)):
            rel = os.path.join(dirname, name)
            if not fnmatch.fnmatch(rel, pattern) or rel in seen:
                continue
            if only is not None and rel not in only:
                continue
            seen.add(rel)
            with open(os.path.join(root, rel)) as f:
                findings.extend(lint_source(f.read(), rel))
    return findings
