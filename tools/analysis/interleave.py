"""Deterministic interleaving explorer for the serving cluster
(ISSUE 7 tentpole, dynamic half — a loom-lite).

The slow-tier cluster tests run real threads under the OS scheduler:
one interleaving per run, usually the same one.  This module replaces
the OS scheduler with a **cooperative, seeded, deterministic** one and
explores MANY interleavings:

* Every thread the cluster creates becomes a managed task parked on
  its own semaphore; exactly one task runs at a time (serialized, so
  every "race" is a *chosen order*, reproducible from the seed).
* **Yield points** — where the scheduler may switch tasks — are the
  cluster's synchronization operations (lock acquire/release, event
  set/clear/wait, thread spawn, clock reads, sleeps) plus, under the
  ``random`` strategy, every traced source line of ``cluster.py``
  (``sys.settrace``; ``sys.monitoring`` would serve on 3.12+).
* **Time is modeled**: ``perf_counter`` returns scheduler time, which
  advances a tick per yield and *jumps* to the earliest timed-wait
  deadline when every task is blocked — so TTL expiry, watchdog
  periods, and idle-loop timeouts execute in microseconds of real time
  and identically on every run.
* **Blocking primitives are scheduler-aware**: a managed task never
  blocks the real OS thread; it marks itself blocked on a predicate
  and hands the token over.  If no task is runnable and no deadline is
  pending, that is a **real deadlock** of the code under test —
  reported as :class:`DeadlockError` with a per-task dump (and proven
  detectable by ``tests/test_interleave.py``'s seeded-deadlock toy).

Injection is scoped, not global: :func:`patch` swaps the ``threading``
and ``time`` module objects *of* ``mxnet_tpu.serving.cluster`` AND
``mxnet_tpu.serving.engine`` for scheduler-aware shims, so jax /
numpy internals keep their real primitives.  (The engine joined the
sweep in round 21: its lock ``_mu`` is what a pipelined ``step()``
and the cluster's submit / cancel threads contend for, so
step-vs-submit-vs-cancel interleavings at pipeline depth 1 are part
of the subject — ``wl_overlap_plan``.  The engine itself starts no
thread.)

Strategies
----------
``random``   pick uniformly among runnable tasks at every sync point;
             additionally preempt at traced ``cluster.py`` lines with
             probability ``line_preempt`` (default 0.1).
``preempt``  force a context switch at every lock acquire/release
             (the targeted mode: maximum contention reordering).

Seed protocol (``docs/static_analysis.md``): a schedule is fully
identified by ``(workload, strategy, seed)``; ``Stats.trace_hash`` is
the sha1 of the (task, kind) yield sequence and must be bit-identical
across runs of the same triple — ``test_deterministic_per_seed`` pins
exactly that.
"""
from __future__ import annotations

import hashlib
import random
import sys
import threading as _real_threading
import time as _real_time
from typing import Callable, Dict, List, Optional

__all__ = ["DeadlockError", "SchedulerShutdown", "Scheduler",
           "Stats", "patch", "run_schedule"]

_RUNNABLE, _BLOCKED, _FINISHED = "runnable", "blocked", "finished"


class DeadlockError(BaseException):
    """Every managed task is blocked and no timed wait can fire.
    Derives BaseException so the cluster's ``except Exception``
    failover path cannot swallow the verdict."""


class SchedulerShutdown(BaseException):
    """Teardown signal for leftover managed tasks."""


class Stats:
    __slots__ = ("yields", "switches", "tasks", "trace_hash",
                 "model_time")

    def __init__(self, yields, switches, tasks, trace_hash,
                 model_time):
        self.yields = yields
        self.switches = switches
        self.tasks = tasks
        self.trace_hash = trace_hash
        self.model_time = model_time

    def __repr__(self):
        return ("Stats(yields=%d, switches=%d, tasks=%d, "
                "trace=%s, t=%.4f)" % (self.yields, self.switches,
                                       self.tasks, self.trace_hash[:12],
                                       self.model_time))


class _Task:
    __slots__ = ("tid", "name", "sem", "state", "pred", "deadline",
                 "reason", "thread", "timed_out")

    def __init__(self, tid, name):
        self.tid = tid
        self.name = name
        self.sem = _real_threading.Semaphore(0)
        self.state = _RUNNABLE
        self.pred: Optional[Callable[[], bool]] = None
        self.deadline: Optional[float] = None
        self.reason = ""
        self.thread: Optional[_real_threading.Thread] = None
        self.timed_out = False


class Scheduler:
    """The cooperative scheduler.  One instance per schedule run."""

    def __init__(self, seed: int, mode: str = "random",
                 line_preempt: float = 0.1):
        if mode not in ("random", "preempt"):
            raise ValueError("mode must be 'random' or 'preempt'")
        self.rng = random.Random(seed)
        self.mode = mode
        self.line_preempt = line_preempt
        self.now = 0.0
        self._mu = _real_threading.Lock()
        self._tasks: Dict[int, _Task] = {}
        self._next_tid = 0
        self._local = _real_threading.local()
        self.abort: Optional[BaseException] = None
        self.yields = 0
        self.switches = 0
        self._sha = hashlib.sha1()
        self.root_done = _real_threading.Event()
        self.root_error: Optional[BaseException] = None
        from mxnet_tpu.serving import cluster as _cluster_mod
        self._traced_file = _cluster_mod.__file__

    # ------------------------------------------------------ plumbing --
    def _me(self) -> Optional[_Task]:
        return getattr(self._local, "task", None)

    def _new_task(self, name) -> _Task:
        task = _Task(self._next_tid, name)
        self._next_tid += 1
        self._tasks[task.tid] = task
        return task

    def _mark(self, tid: int, kind: str):
        self._sha.update(("%d:%s;" % (tid, kind)).encode())

    def _check_abort(self):
        if self.abort is not None:
            raise self.abort

    # the per-thread trace functions (sys.settrace): 'line' events in
    # cluster.py are extra yield points under the random strategy
    def _global_trace(self, frame, event, arg):
        if event == "call" and \
                frame.f_code.co_filename == self._traced_file:
            return self._local_trace
        return None

    def _local_trace(self, frame, event, arg):
        if event == "line":
            self.yield_point("line")
        return self._local_trace

    # ----------------------------------------------------- the core --
    def _promote_locked(self):
        """BLOCKED tasks whose predicate turned true become runnable;
        expired deadlines fire."""
        for t in self._tasks.values():
            if t.state != _BLOCKED:
                continue
            if t.pred is not None and t.pred():
                t.state = _RUNNABLE
                t.pred = None
                t.deadline = None
            elif t.deadline is not None and self.now >= t.deadline:
                t.state = _RUNNABLE
                t.pred = None
                t.deadline = None
                t.timed_out = True

    def _runnable_locked(self) -> List[_Task]:
        self._promote_locked()
        return [t for t in self._tasks.values()
                if t.state == _RUNNABLE]

    def _advance_or_deadlock_locked(self) -> List[_Task]:
        """No runnable task: jump model time to the earliest deadline,
        or declare deadlock."""
        deadlines = [t.deadline for t in self._tasks.values()
                     if t.state == _BLOCKED and t.deadline is not None]
        if deadlines:
            self.now = max(self.now, min(deadlines))
            return self._runnable_locked()
        live = [t for t in self._tasks.values()
                if t.state != _FINISHED]
        if not live:
            return []
        dump = "; ".join(
            "task %d (%s): blocked on %s" % (t.tid, t.name, t.reason)
            for t in sorted(live, key=lambda t: t.tid))
        err = DeadlockError(
            "all %d live task(s) blocked with no timed wait — "
            "deadlock: %s" % (len(live), dump))
        self.abort = err
        for t in self._tasks.values():
            if t.state != _FINISHED:
                t.sem.release()
        raise err

    def _choose_locked(self, candidates: List[_Task], cur: _Task,
                       kind: str) -> _Task:
        candidates = sorted(candidates, key=lambda t: t.tid)
        if self.mode == "preempt":
            if kind in ("acquire", "release"):
                others = [t for t in candidates if t is not cur]
                pool = others or candidates
            else:
                pool = [cur] if cur in candidates else candidates
            return self.rng.choice(pool)
        # random strategy
        if kind == "line":
            if self.rng.random() >= self.line_preempt:
                return cur if cur in candidates else \
                    self.rng.choice(candidates)
        return self.rng.choice(candidates)

    def yield_point(self, kind: str):
        task = self._me()
        if task is None:
            return                      # unmanaged thread: no-op
        self._check_abort()
        nxt = None
        with self._mu:
            self.yields += 1
            self.now += 1e-7
            self._mark(task.tid, kind)
            candidates = self._runnable_locked()
            chosen = self._choose_locked(candidates, task, kind)
            if chosen is not task:
                self.switches += 1
                self._mark(chosen.tid, "run")
                nxt = chosen
                nxt.sem.release()
        if nxt is not None:
            task.sem.acquire()
            self._check_abort()

    def block_until(self, pred: Callable[[], bool],
                    timeout: Optional[float], reason: str) -> bool:
        """Park the current task until ``pred()`` holds or the model
        deadline passes.  Returns what ``Event.wait`` would."""
        task = self._me()
        if task is None:
            raise RuntimeError(
                "block_until from an unmanaged thread (reason=%s) — "
                "run the workload inside run_schedule()" % reason)
        deadline = None if timeout is None else self.now + timeout
        while True:
            with self._mu:
                self._check_abort()
                if pred():
                    return True
                if deadline is not None and self.now >= deadline:
                    return False
                task.state = _BLOCKED
                task.pred = pred
                task.deadline = deadline
                task.reason = reason
                task.timed_out = False
                self._mark(task.tid, "block:" + reason)
                candidates = [t for t in self._runnable_locked()
                              if t is not task]
                if not candidates:
                    candidates = [t for t in
                                  self._advance_or_deadlock_locked()
                                  if t is not task]
                if task.state == _RUNNABLE:
                    # our own deadline fired during the jump
                    if task.timed_out:
                        return pred()
                    continue
                nxt = self.rng.choice(sorted(candidates,
                                             key=lambda t: t.tid))
                self.switches += 1
                self._mark(nxt.tid, "run")
                nxt.sem.release()
            task.sem.acquire()
            self._check_abort()

    def task_finished(self):
        task = self._me()
        with self._mu:
            task.state = _FINISHED
            self._mark(task.tid, "finish")
            if self.abort is not None:
                return
            candidates = self._runnable_locked()
            if not candidates:
                live = [t for t in self._tasks.values()
                        if t.state != _FINISHED]
                if not live:
                    return
                try:
                    candidates = self._advance_or_deadlock_locked()
                except DeadlockError:
                    return          # abort propagated to woken tasks
                if not candidates:
                    return
            nxt = self.rng.choice(sorted(candidates,
                                         key=lambda t: t.tid))
            self._mark(nxt.tid, "run")
            nxt.sem.release()

    # ------------------------------------------------------- spawning --
    def _boot(self, task: _Task, target, args, kwargs):
        self._local.task = task
        if self.mode == "random" and self.line_preempt > 0:
            sys.settrace(self._global_trace)
        task.sem.acquire()              # wait for the first grant
        try:
            self._check_abort()
            target(*args, **kwargs)
        except BaseException as e:      # noqa: BLE001
            if task.name == "<root>":
                self.root_error = e
            elif self.abort is None and not isinstance(
                    e, SchedulerShutdown):
                # a non-root task target raised PAST the cluster's own
                # exception handling — a harness or model bug, never a
                # legal schedule outcome (replica failure is caught
                # inside _worker): abort the schedule loudly
                with self._mu:
                    if self.abort is None:
                        self.abort = e
                        for t in self._tasks.values():
                            if t.state != _FINISHED:
                                t.sem.release()
        finally:
            if task.name == "<root>":
                self.root_done.set()
            self.task_finished()

    def spawn(self, name, target, args=(), kwargs=None) -> _Task:
        with self._mu:
            task = self._new_task(name)
            self._mark(task.tid, "spawn")
        th = _real_threading.Thread(
            target=self._boot, args=(task, target, args, kwargs or {}),
            daemon=True, name="ilv-%s" % name)
        task.thread = th
        th.start()
        return task

    def start_root(self, target):
        root = self.spawn("<root>", target)
        with self._mu:
            root.sem.release()          # root runs first
        return root

    def shutdown(self):
        with self._mu:
            if self.abort is None:
                self.abort = SchedulerShutdown("schedule over")
            for t in self._tasks.values():
                if t.state != _FINISHED:
                    t.sem.release()
        for t in self._tasks.values():
            if t.thread is not None:
                t.thread.join(timeout=5)

    def stats(self) -> Stats:
        return Stats(self.yields, self.switches, len(self._tasks),
                     self._sha.hexdigest(), self.now)


# ---------------------------------------------------------------------------
# scheduler-aware primitives (what the cluster sees as `threading`/`time`)
# ---------------------------------------------------------------------------
class SchedLock:
    _reentrant = False

    def __init__(self, sched: Scheduler):
        self._sched = sched
        self._owner: Optional[int] = None
        self._count = 0

    def acquire(self, blocking=True, timeout=-1):
        sched = self._sched
        task = sched._me()
        if task is None:
            raise RuntimeError("SchedLock from unmanaged thread")
        sched.yield_point("acquire")
        if self._owner == task.tid and self._reentrant:
            self._count += 1
            return True
        if self._owner is None:
            self._owner = task.tid
            self._count = 1
            return True
        if not blocking:
            return False
        ok = sched.block_until(
            lambda: self._owner is None,
            None if timeout in (-1, None) else timeout, "lock")
        if not ok:
            return False
        self._owner = task.tid
        self._count = 1
        return True

    def release(self):
        task = self._sched._me()
        if self._owner != (task.tid if task else None):
            raise RuntimeError("release of un-owned SchedLock")
        self._count -= 1
        if self._count == 0:
            self._owner = None
        self._sched.yield_point("release")

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *a):
        self.release()
        return False


class SchedRLock(SchedLock):
    _reentrant = True


class SchedEvent:
    """Model event with a REAL mirror so unmanaged threads (none in
    the explorer's own runs, but belt-and-braces) still wake."""

    def __init__(self, sched: Scheduler):
        self._sched = sched
        self._flag = False
        self._real = _real_threading.Event()

    def is_set(self):
        return self._flag

    def set(self):
        self._flag = True
        self._real.set()
        self._sched.yield_point("event-set")

    def clear(self):
        self._flag = False
        self._real.clear()
        self._sched.yield_point("event-clear")

    def wait(self, timeout=None):
        if self._sched._me() is None:
            return self._real.wait(timeout)
        if self._flag:
            self._sched.yield_point("event-wait")
            return True
        return self._sched.block_until(lambda: self._flag, timeout,
                                       "event")


class SchedThread:
    """threading.Thread stand-in: start() registers a managed task."""

    def __init__(self, sched=None, target=None, args=(), kwargs=None,
                 daemon=None, name=None):
        self._sched = sched
        self._target = target
        self._args = args
        self._kwargs = kwargs or {}
        self.daemon = daemon
        self.name = name or "sched-thread"
        self._task: Optional[_Task] = None

    def start(self):
        self._task = self._sched.spawn(self.name, self._target,
                                       self._args, self._kwargs)
        self._sched.yield_point("spawn")

    def is_alive(self):
        return self._task is not None and \
            self._task.state != _FINISHED

    def join(self, timeout=None):
        task = self._task
        if task is None:
            return
        if self._sched._me() is None:
            if task.thread is not None:
                task.thread.join(timeout)
            return
        self._sched.block_until(lambda: task.state == _FINISHED,
                                timeout, "join:%s" % self.name)


class _ThreadingShim:
    def __init__(self, sched: Scheduler):
        self._sched = sched

    def Thread(self, target=None, args=(), kwargs=None, daemon=None,
               name=None):
        return SchedThread(self._sched, target, args, kwargs, daemon,
                           name)

    def Event(self):
        return SchedEvent(self._sched)

    def Lock(self):
        return SchedLock(self._sched)

    def RLock(self):
        return SchedRLock(self._sched)


class _TimeShim:
    def __init__(self, sched: Scheduler):
        self._sched = sched

    def perf_counter(self):
        self._sched.yield_point("clock")
        return self._sched.now

    def sleep(self, t):
        if self._sched._me() is None:
            _real_time.sleep(t)
            return
        self._sched.block_until(lambda: False, max(0.0, float(t)),
                                "sleep")


class patch:
    """Context manager: swap ``mxnet_tpu.serving.cluster``'s and
    ``mxnet_tpu.serving.engine``'s module references to ``threading``
    / ``time`` for scheduler shims (the engine's lock is under sweep
    since round 21)."""

    def __init__(self, sched: Scheduler):
        self.sched = sched

    def __enter__(self):
        from mxnet_tpu.serving import cluster, engine
        self._mods = (cluster, engine)
        self._saved = [(m.threading, m.time) for m in self._mods]
        shims = (_ThreadingShim(self.sched), _TimeShim(self.sched))
        for m in self._mods:
            m.threading, m.time = shims
        return self.sched

    def __exit__(self, *a):
        for m, (th, tm) in zip(self._mods, self._saved):
            m.threading, m.time = th, tm
        return False


def run_schedule(workload: Callable[[], None], seed: int,
                 mode: str = "random", line_preempt: float = 0.1,
                 real_timeout: float = 300.0) -> Stats:
    """Run ``workload()`` (which builds, drives, and closes a
    ``ServingCluster``) under one deterministic schedule.

    Raises whatever the workload raises (assertion failures surface
    with the seed in the pytest parameterization), ``DeadlockError``
    on a model deadlock, and ``RuntimeError`` if the schedule exceeds
    ``real_timeout`` real seconds (a hang the model cannot see —
    e.g. a real primitive smuggled past the shims)."""
    sched = Scheduler(seed, mode=mode, line_preempt=line_preempt)
    with patch(sched):
        sched.start_root(workload)
        finished = sched.root_done.wait(real_timeout)
        if not finished:
            sched.shutdown()
            raise RuntimeError(
                "interleave: schedule (seed=%d, mode=%s) still "
                "running after %.0fs real time — %r"
                % (seed, mode, real_timeout, sched.stats()))
        # let the cluster's own threads wind down (workloads close()
        # before returning, so normally everything is finished here)
        sched.shutdown()
    if sched.root_error is not None:
        raise sched.root_error
    return sched.stats()
