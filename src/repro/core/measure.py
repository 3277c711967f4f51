"""Measurement backends — the paper's "compile it, run it, time it" stage (§IV-C).

Every backend maps (workload, configuration) → :class:`Result`:

* legality is checked first (Polly dependence analysis analogue) — failures are
  ``illegal`` red nodes;
* structural codegen failures are ``compile_error`` red nodes (Clang
  ``-Werror=pass-failed`` analogue);
* runtime/timeout failures are ``exec_error`` red nodes;
* success carries the measured/predicted time in seconds.

Backends:

* :class:`CostModelBackend` — deterministic analytic model (Xeon-8180M for
  paper fidelity, TPU-v5e for kernel tuning).  Used for the paper-reproduction
  figures since this container has one CPU core.
* :class:`WallclockBackend` — real execution of the XLA:CPU tiled codegen at a
  reduced problem scale; cross-checks the model's tiling/interchange rankings.
* :class:`PallasBackend` — compiles the Pallas kernel with Mosaic on a TPU
  (or, when asked, runs it in the Pallas interpreter at a reduced scale),
  verifies it against the jnp oracle, and reports the TPU cost-model time;
  tiles over the VMEM limit and Mosaic's own refusals are ``compile_error``.

Batching model
--------------
``evaluate_many`` has three dispatch paths:

* **sequential** — the default, and the only honest option for wall-clock
  timing inside one process;
* **thread pool** (:class:`_ThreadedEvalMixin`) — for backends whose reported
  time is *deterministic* (Pallas scores with the TPU cost model and only
  verifies concurrently).  :class:`WallclockBackend` **rejects**
  ``max_workers > 1`` outright: concurrent timed runs in one process contend
  for cores and skew every sample;
* **supervised process pool** (:class:`SupervisedPool`, engaged by
  ``process_workers=N``) — each worker is a separate process pinned to its
  own CPU core via ``os.sched_setaffinity``, so timed runs proceed in
  parallel without sharing a core.  Workers rebuild the backend from a small
  picklable spec (:meth:`WallclockBackend.worker_spec`); workloads/
  configurations are plain frozen dataclasses and pickle as-is.  Unlike a
  plain executor, the supervisor enforces a **hard per-task deadline**: a
  worker that overruns it is SIGKILLed and respawned (re-claiming its freed
  core), and the overrun becomes an ``exec_error("timeout ...")`` red node —
  a hung kernel can no longer block the run.  Repeated worker deaths trip a
  circuit breaker that degrades to serial measurement with an explicit
  ``faults["degraded"]`` marker.  When pinning is impossible (no
  ``sched_setaffinity``, pool startup failure) the call falls back to the
  sequential path — results are identical, only slower — and the fallback
  is *counted* (``faults["serial_fallbacks"]``) and warned once, never
  silent.

Persistence: every backend also exposes :meth:`Backend.store_scope`, the
identity string under which its measurements are recorded in the on-disk
:class:`~repro.core.resultstore.ResultStore` (deterministic model backends are
host-independent; wallclock scopes embed the host fingerprint and scale).
"""

from __future__ import annotations

import collections
import logging
import multiprocessing
import os
import shutil
import tempfile
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Sequence

import numpy as np

from . import codegen
from .costmodel import Machine, TPU_V5E, XEON_8180M, estimate_time
from .legality import IllegalTransform, check_legal
from .loopnest import LoopNest
from .searchspace import Configuration
from .transformations import TransformError
from .workloads import Workload

_log = logging.getLogger("repro.core.measure")


@dataclass(frozen=True)
class Result:
    status: str                 # ok | illegal | compile_error | exec_error
    time_s: float | None = None
    note: str = ""

    @property
    def ok(self) -> bool:
        return self.status == "ok"


class Backend:
    """Maps (workload, configuration) → :class:`Result`.

    ``evaluate`` accepts an optional pre-derived ``nest`` so callers that
    already hold the post-transformation structure (the evaluation engine's
    incremental prefix cache) skip the replay-from-root; legality is always
    re-checked against the nest actually measured.  ``evaluate_many`` is the
    batched entry point — sequential here, thread-pooled in the backends where
    compile+measure dominates (see :class:`_ThreadedEvalMixin`).
    """

    name = "abstract"

    def evaluate(
        self,
        workload: Workload,
        config: Configuration,
        nest: LoopNest | None = None,
    ) -> Result:
        if nest is None:
            try:
                nest = config.apply(workload.nest())
            except TransformError as e:
                return Result("compile_error", note=str(e))
        try:
            check_legal(nest)
        except IllegalTransform as e:
            return Result("illegal", note=str(e))
        return self._measure(workload, nest)

    def evaluate_many(
        self,
        workload: Workload,
        configs: Sequence[Configuration],
        nests: Sequence[LoopNest | None] | None = None,
    ) -> list[Result]:
        """Evaluate a batch of configurations, preserving order."""
        if nests is None:
            nests = [None] * len(configs)
        return [self.evaluate(workload, c, nest=n) for c, n in zip(configs, nests)]

    def _measure(self, workload: Workload, nest: LoopNest) -> Result:
        raise NotImplementedError

    def store_scope(self) -> str:
        """Identity under which this backend's results are persisted in the
        :class:`~repro.core.resultstore.ResultStore`.

        Must cover everything that affects the measured/predicted time.  The
        generic fallback is conservative: backend name + host fingerprint.
        Deterministic model backends override this to a host-independent
        scope; wallclock backends embed the host and problem scale."""
        from .resultstore import host_fingerprint

        return f"{self.name}@{host_fingerprint()}"


# ---------------------------------------------------------------------------
# Supervised process-parallel evaluation: one killable worker per CPU core.
# ---------------------------------------------------------------------------


def _usable_cores() -> list[int]:
    """CPU cores this process may schedule on (affinity-aware)."""
    try:
        return sorted(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return list(range(os.cpu_count() or 1))


#: Builders from which a supervised worker process reconstructs its backend:
#: ``kind -> callable(**spec)``.  Extend via :func:`register_worker_backend`
#: (:mod:`repro.core.faults` registers the ``"fault"`` injection wrapper).
_WORKER_BACKEND_BUILDERS: dict[str, Callable[..., "Backend"]] = {}


def register_worker_backend(kind: str,
                            builder: Callable[..., "Backend"]) -> None:
    """Register a builder a :class:`SupervisedPool` worker uses to rebuild a
    backend from its picklable ``(kind, spec)`` pair."""
    _WORKER_BACKEND_BUILDERS[kind] = builder


def build_worker_backend(kind: str, spec: dict) -> "Backend":
    """Construct a backend from its picklable worker spec (worker side)."""
    builder = _WORKER_BACKEND_BUILDERS.get(kind)
    if builder is None and kind == "fault":
        from . import faults  # noqa: F401 — importing registers "fault"

        builder = _WORKER_BACKEND_BUILDERS.get(kind)
    if builder is None:
        raise ValueError(
            f"unknown worker backend kind {kind!r} "
            f"(registered: {', '.join(sorted(_WORKER_BACKEND_BUILDERS))})")
    return builder(**spec)


def _claim_core(lockdir: str | None, cores: Sequence[int]) -> int | None:
    """Claim a dedicated CPU core and pin the calling process to it.

    Core claiming uses ``O_CREAT|O_EXCL`` lock files in a pool-private
    directory — the only cross-process primitive that survives the ``spawn``
    start method without inheriting handles.  The process pins itself to the
    first unclaimed core, so no two timed runs ever share one; when a hung
    worker is killed, the supervisor deletes its lock file so the respawned
    worker re-claims the freed core.  If claiming or pinning fails the
    worker still evaluates correctly, just unpinned (returns ``None``).
    """
    if lockdir is None:
        return None
    pinned = None
    for c in cores:
        try:
            fd = os.open(
                os.path.join(lockdir, f"cpu{c}.lock"),
                os.O_CREAT | os.O_EXCL | os.O_WRONLY,
            )
            os.close(fd)
            pinned = c
            break
        except FileExistsError:
            continue
        except OSError:
            break
    if pinned is not None:
        try:
            os.sched_setaffinity(0, {pinned})
        except (AttributeError, OSError):
            pinned = None
    return pinned


def _supervised_worker_main(conn, kind: str, spec: dict,
                            lockdir: str | None,
                            cores: tuple[int, ...]) -> None:
    """Worker loop: claim a core, rebuild the backend, answer tasks.

    Protocol (one request, one response, over the duplex pipe): the worker
    first sends ``("ready", pinned_core, pid)`` (or ``("init_error", msg,
    pid)``), then answers each ``(workload, config)`` task with a
    :class:`Result`.  ``None`` or a closed pipe ends the loop.  Exceptions
    raised by the backend become ``exec_error`` results — a worker answers,
    it never dies of a task (dying is reserved for real crashes, which the
    supervisor detects as an EOF)."""
    pinned = _claim_core(lockdir, cores)
    try:
        backend = build_worker_backend(kind, spec)
    except Exception as e:  # noqa: BLE001 — report, don't traceback-spam
        try:
            conn.send(("init_error", f"{type(e).__name__}: {e}", os.getpid()))
        except (OSError, BrokenPipeError):
            pass
        return
    try:
        conn.send(("ready", pinned, os.getpid()))
    except (OSError, BrokenPipeError):
        return
    while True:
        try:
            task = conn.recv()
        except (EOFError, OSError):
            return
        if task is None:
            return
        workload, config = task
        try:
            res = backend.evaluate(workload, config)
        except Exception as e:  # noqa: BLE001
            res = Result("exec_error",
                         note=f"worker exception: {type(e).__name__}: {e}")
        try:
            conn.send(res)
        except (EOFError, OSError, BrokenPipeError):
            return


class _SupervisedWorker:
    """One spawned measurement process plus its supervisor-side pipe end."""

    def __init__(self, ctx, kind: str, spec: dict, lockdir: str | None,
                 cores: Sequence[int]):
        self.conn, child = ctx.Pipe(duplex=True)
        self.proc = ctx.Process(
            target=_supervised_worker_main,
            args=(child, kind, spec, lockdir, tuple(cores)),
            daemon=True,
        )
        self.proc.start()
        child.close()
        self.core: int | None = None
        self.ready = False

    def ensure_ready(self, timeout: float) -> bool:
        """Wait for the startup handshake (backend built, core claimed).
        False → the worker is unusable and must be retired."""
        if self.ready:
            return True
        try:
            if not self.conn.poll(timeout):
                return False
            msg = self.conn.recv()
        except (EOFError, OSError):
            return False
        if not (isinstance(msg, tuple) and msg and msg[0] == "ready"):
            return False
        self.core = msg[1]
        self.ready = True
        return True

    def kill(self, lockdir: str | None) -> None:
        """Hard-kill the process and release its claimed core's lock file so
        a respawned worker can re-claim the core."""
        try:
            self.proc.kill()
        except Exception:  # noqa: BLE001
            pass
        self.proc.join(timeout=10.0)
        try:
            self.conn.close()
        except Exception:  # noqa: BLE001
            pass
        if lockdir is not None and self.core is not None:
            try:
                os.unlink(os.path.join(lockdir, f"cpu{self.core}.lock"))
            except OSError:
                pass


class SupervisedPool:
    """Kill-capable measurement pool: core-pinned worker processes driven
    over pipes, with a hard per-task deadline.

    The old executor-based path could not preempt a hung measurement —
    ``timeout_s`` was only checked *after* the first rep returned, so a
    genuinely hung kernel blocked the run forever.  Here the supervisor
    waits ``deadline_s`` per task and, on overrun, SIGKILLs the worker,
    releases its CPU-core lock file, and lazily respawns a replacement
    (which re-claims the freed core); the overrun becomes an
    ``exec_error("timeout ...")`` red node.  A worker that *dies* mid-task
    is respawned and the task retried once at this layer (transient-failure
    policy beyond that lives in the engine's ``RetryPolicy``).

    Fault accounting lands in the shared ``faults`` dict
    (``deadline_kills`` / ``pool_deaths`` / ``serial_fallbacks`` /
    ``deadline_skips`` / ``degraded``) — the engine surfaces it in
    ``TuningLog.cache["faults"]``.  ``breaker`` worker deaths trip a circuit
    breaker: the pool marks itself ``broken``, sets ``faults["degraded"]``,
    and remaining tasks go through ``serial_fallback`` (in-process
    evaluation) when one is provided, else become red nodes — degraded, but
    loudly.
    """

    def __init__(
        self,
        kind: str,
        spec: dict,
        workers: int = 1,
        *,
        deadline_s: float | None = None,
        mp_start_method: str = "spawn",
        breaker: int = 3,
        faults: dict | None = None,
        serial_fallback: Callable[["Workload", "Configuration"],
                                  "Result"] | None = None,
        startup_timeout: float = 180.0,
    ):
        self.kind = kind
        self.spec = dict(spec)
        self.deadline_s = deadline_s
        self.breaker = breaker
        self.faults = faults if faults is not None else {}
        self.serial_fallback = serial_fallback
        self.startup_timeout = startup_timeout
        self.broken = False
        self.lockdir = tempfile.mkdtemp(prefix="repro-cpupin-")
        self._cores = tuple(_usable_cores())
        self._ctx = multiprocessing.get_context(mp_start_method)
        self._lock = threading.Lock()
        self._workers: list[_SupervisedWorker | None] = [
            self._spawn() for _ in range(max(1, workers))]
        # per-slot utilization (busy seconds, tasks served, deadline kills)
        # — surfaced via utilization() into TuningLog.cache["pool"]
        self._t_started = time.monotonic()
        self._util: list[dict] = [
            {"busy_s": 0.0, "tasks": 0, "kills": 0}
            for _ in range(max(1, workers))]
        # streaming submit() state: a shared FIFO drained by one dispatcher
        # thread per worker slot (started lazily on the first submit)
        self._task_q: collections.deque = collections.deque()
        self._task_cv = threading.Condition()
        self._dispatchers: list[threading.Thread] = []
        self._closing = False

    # -- lifecycle -----------------------------------------------------------

    def _spawn(self) -> _SupervisedWorker | None:
        try:
            return _SupervisedWorker(
                self._ctx, self.kind, self.spec, self.lockdir, self._cores)
        except Exception:  # noqa: BLE001 — spawn failure handled as a death
            return None

    def _worker(self, slot: int) -> _SupervisedWorker | None:
        if self._workers[slot] is None:
            self._workers[slot] = self._spawn()
        return self._workers[slot]

    def _retire(self, slot: int) -> None:
        w = self._workers[slot]
        if w is not None:
            w.kill(self.lockdir)
        self._workers[slot] = None

    def _count(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.faults[key] = self.faults.get(key, 0) + n

    def _note_death(self) -> None:
        with self._lock:
            self.faults["pool_deaths"] = self.faults.get("pool_deaths", 0) + 1
            if (not self.broken
                    and self.faults["pool_deaths"] >= self.breaker):
                self.broken = True
                self.faults["degraded"] = 1
                _log.warning(
                    "supervised pool (%s): %d worker death(s) — circuit "
                    "breaker tripped, degrading to %s", self.kind,
                    self.faults["pool_deaths"],
                    "serial in-process measurement"
                    if self.serial_fallback is not None
                    else "red nodes (no serial fallback)")

    def close(self) -> None:
        """Kill every worker and release the core-claim directory.  Any
        queued-but-unstarted streaming tasks resolve to ``exec_error`` red
        results (a closed pool never leaves a future dangling)."""
        with self._task_cv:
            self._closing = True
            self._task_cv.notify_all()
        for t in self._dispatchers:
            t.join(timeout=30.0)
        self._dispatchers = []
        while True:
            with self._task_cv:
                task = self._task_q.popleft() if self._task_q else None
            if task is None:
                break
            fut = task[0]
            if fut.set_running_or_notify_cancel():
                fut.set_result(Result("exec_error", note="pool closed"))
        for slot in range(len(self._workers)):
            self._retire(slot)
        shutil.rmtree(self.lockdir, ignore_errors=True)

    # -- dispatch ------------------------------------------------------------

    def warmup(self, timeout: float | None = None) -> int:
        """Block until every worker finished its startup handshake; returns
        the number that came up ready.  Benchmarks call this so pool spawn
        cost (one interpreter + JAX import per worker) is excluded from the
        measured tuning wall clock."""
        t = self.startup_timeout if timeout is None else timeout
        ready = 0
        for slot in range(len(self._workers)):
            w = self._worker(slot)
            if w is not None and w.ensure_ready(t):
                ready += 1
        return ready

    def submit(
        self,
        workload: "Workload",
        config: "Configuration",
        deadline_at: float | None = None,
    ) -> "Future[Result]":
        """Streaming entry point: enqueue one task and return a
        :class:`~concurrent.futures.Future` that resolves to its
        :class:`Result`.  One dispatcher thread per worker slot drains the
        shared queue, so up to ``workers`` tasks run concurrently and a
        future completes the moment *its* measurement lands — the async
        session observes results out of submission order.

        ``deadline_at`` is an absolute ``time.monotonic()`` budget horizon
        (the session's remaining ``max_seconds``): tasks that cannot start
        before it become ``exec_error`` red nodes, exactly like the batch
        deadline in :meth:`run`.  Deadlines, kill/respawn, and the circuit
        breaker are the same machinery — the dispatcher reuses
        :meth:`_run_one`.  Futures never carry exceptions; every outcome is
        a :class:`Result`.  Do not interleave :meth:`submit` with a
        concurrent :meth:`run` call — both would drive the same worker
        slots."""
        fut: "Future[Result]" = Future()
        with self._task_cv:
            if self._closing:
                fut.set_result(Result("exec_error", note="pool closed"))
                return fut
            self._task_q.append((fut, workload, config, deadline_at))
            if len(self._dispatchers) < len(self._workers):
                slot = len(self._dispatchers)
                t = threading.Thread(
                    target=self._dispatch_loop, args=(slot,), daemon=True)
                self._dispatchers.append(t)
                t.start()
            self._task_cv.notify()
        return fut

    def _dispatch_loop(self, slot: int) -> None:
        while True:
            with self._task_cv:
                while not self._task_q and not self._closing:
                    self._task_cv.wait()
                if self._closing:
                    return      # close() red-flags whatever is still queued
                fut, workload, config, deadline_at = self._task_q.popleft()
            if not fut.set_running_or_notify_cancel():
                continue
            if deadline_at is not None and time.monotonic() >= deadline_at:
                self._count("deadline_skips")
                fut.set_result(
                    Result("exec_error", note="timeout (batch deadline)"))
                continue
            if self.broken:
                fut.set_result(self._serial_eval(workload, config))
                continue
            res = self._timed_run_one(slot, workload, config, deadline_at)
            fut.set_result(res if res is not None
                           else self._serial_eval(workload, config))

    def utilization(self) -> dict:
        """Pool utilization snapshot for ``TuningLog.cache["pool"]``:
        per-worker busy/idle seconds, tasks served, and deadline kills,
        plus the aggregate busy fraction over the pool's lifetime."""
        wall = max(time.monotonic() - self._t_started, 1e-9)
        with self._lock:
            per = [
                {"busy_s": round(u["busy_s"], 4),
                 "idle_s": round(max(0.0, wall - u["busy_s"]), 4),
                 "tasks": u["tasks"], "kills": u["kills"]}
                for u in self._util]
        busy = sum(u["busy_s"] for u in per)
        return {
            "workers": len(per),
            "wall_s": round(wall, 4),
            "busy_s": round(busy, 4),
            "tasks": sum(u["tasks"] for u in per),
            "kills": sum(u["kills"] for u in per),
            "busy_frac": round(busy / (wall * len(per)), 4),
            "per_worker": per,
        }

    def _serial_eval(self, workload: "Workload",
                     config: "Configuration") -> "Result":
        self._count("serial_fallbacks")
        if self.serial_fallback is None:
            return Result(
                "exec_error",
                note="worker died (supervised pool broken, "
                     "no serial fallback)")
        try:
            return self.serial_fallback(workload, config)
        except Exception as e:  # noqa: BLE001
            return Result(
                "exec_error",
                note=f"serial fallback failed: {type(e).__name__}: {e}")

    def run(
        self,
        workload: "Workload",
        configs: "Sequence[Configuration]",
        batch_deadline_s: float | None = None,
    ) -> "list[Result]":
        """Evaluate a batch, order-preserving.  ``batch_deadline_s`` bounds
        the *whole batch* (the session's remaining ``max_seconds`` is passed
        down here): tasks that cannot start before it expires become
        ``exec_error`` red nodes instead of overshooting the budget."""
        results: list[Result | None] = [None] * len(configs)
        batch_end = (time.monotonic() + batch_deadline_s
                     if batch_deadline_s is not None else None)
        pending = list(range(len(configs)))
        qlock = threading.Lock()

        def next_index() -> int | None:
            with qlock:
                return pending.pop(0) if pending else None

        def drive(slot: int) -> None:
            while True:
                i = next_index()
                if i is None:
                    return
                if batch_end is not None and time.monotonic() >= batch_end:
                    self._count("deadline_skips")
                    results[i] = Result(
                        "exec_error", note="timeout (batch deadline)")
                    continue
                if self.broken:
                    results[i] = self._serial_eval(workload, configs[i])
                    continue
                res = self._timed_run_one(slot, workload, configs[i],
                                          batch_end)
                results[i] = (res if res is not None
                              else self._serial_eval(workload, configs[i]))

        if len(self._workers) == 1:
            drive(0)
        else:
            threads = [threading.Thread(target=drive, args=(s,), daemon=True)
                       for s in range(len(self._workers))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        return results  # type: ignore[return-value]

    def _timed_run_one(self, slot: int, workload: "Workload",
                       config: "Configuration",
                       batch_end: float | None) -> "Result | None":
        t0 = time.monotonic()
        try:
            return self._run_one(slot, workload, config, batch_end)
        finally:
            with self._lock:
                u = self._util[slot]
                u["busy_s"] += time.monotonic() - t0
                u["tasks"] += 1

    def _run_one(self, slot: int, workload: "Workload",
                 config: "Configuration",
                 batch_end: float | None) -> "Result | None":
        # one respawn retry per task: a worker death mid-task is retried on
        # a fresh worker once before giving up (None → caller falls back)
        for _attempt in range(2):
            if self.broken:
                return None
            w = self._worker(slot)
            if w is None or not w.ensure_ready(self.startup_timeout):
                self._retire(slot)
                self._note_death()
                continue
            try:
                w.conn.send((workload, config))
            except (OSError, BrokenPipeError, ValueError):
                self._retire(slot)
                self._note_death()
                continue
            wait = self.deadline_s
            if batch_end is not None:
                remaining = batch_end - time.monotonic()
                wait = remaining if wait is None else min(wait, remaining)
            if wait is not None:
                wait = max(wait, 0.001)
            try:
                arrived = w.conn.poll(wait)
            except (OSError, EOFError):
                arrived = False
            if not arrived:
                if w.proc.is_alive():
                    # hard overrun: kill, release the core, respawn lazily
                    self._retire(slot)
                    self._count("deadline_kills")
                    with self._lock:
                        self._util[slot]["kills"] += 1
                    return Result(
                        "exec_error",
                        note=f"timeout (worker killed after {wait:.1f}s "
                             f"hard deadline)")
                self._retire(slot)
                self._note_death()
                continue
            try:
                msg = w.conn.recv()
            except (EOFError, OSError):
                self._retire(slot)
                self._note_death()
                continue
            if isinstance(msg, Result):
                return msg
            self._retire(slot)      # protocol garbage — treat as a death
            self._note_death()
        return None

    def __enter__(self) -> "SupervisedPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class _ThreadedEvalMixin:
    """Thread-pooled ``evaluate_many`` for backends whose per-experiment cost
    is dominated by compile+measure (XLA tracing/compilation, Pallas interpret
    verification) rather than Python work.

    ``max_workers`` gates the pool: ``<= 1`` keeps the sequential path.  Note
    for wall-clock timing backends: concurrent timed runs contend for cores
    and skew measurements, so :class:`WallclockBackend` *rejects*
    ``max_workers > 1`` at construction (use its core-pinned
    ``process_workers`` path instead); :class:`PallasBackend` scores with the
    deterministic TPU cost model and only *verifies* concurrently, so its
    thread pool is on by default.
    """

    max_workers: int = 1

    def evaluate_many(
        self,
        workload: Workload,
        configs: Sequence[Configuration],
        nests: Sequence[LoopNest | None] | None = None,
    ) -> list[Result]:
        if nests is None:
            nests = [None] * len(configs)
        if len(configs) <= 1 or self.max_workers <= 1:
            return [
                self.evaluate(workload, c, nest=n)
                for c, n in zip(configs, nests)
            ]
        with ThreadPoolExecutor(
            max_workers=min(self.max_workers, len(configs))
        ) as pool:
            futs = [
                pool.submit(self.evaluate, workload, c, nest=n)
                for c, n in zip(configs, nests)
            ]
            return [f.result() for f in futs]


class _SupervisedMeasureMixin:
    """Shared :class:`SupervisedPool` plumbing for measurement backends.

    Hosts the batch-deadline hand-off (the session's remaining
    ``max_seconds`` becomes a per-batch measurement deadline), the pool
    lifecycle (``_pool`` / ``_pool_lockdir`` / ``_pool_broken``), and the
    loud serial-fallback accounting.  The concrete backend declares the
    dataclass fields (``process_workers``, ``faults``, ...) and supplies
    :meth:`worker_spec` / :meth:`_pool_deadline`.
    """

    #: last pool utilization snapshot, kept across close() so the session
    #: can surface it in TuningLog.cache["pool"] after the pool is gone
    _last_pool_util = None

    def worker_spec(self) -> dict:
        """Picklable constructor kwargs from which a pool worker rebuilds
        this backend (pool fields intentionally excluded — workers evaluate
        sequentially on their pinned core)."""
        raise NotImplementedError

    def _pool_deadline(self) -> float | None:
        """Per-task hard kill deadline for supervised workers."""
        return None

    def _pool_requires_pinning(self) -> bool:
        """True when the pool is pointless without core pinning (honest
        wall-clock timing); deterministic backends run unpinned fine."""
        return False

    def set_batch_deadline(self, seconds: float | None) -> None:
        """Arm a deadline for the *next* ``evaluate_many`` batch only — the
        session passes its remaining ``max_seconds`` here so one slow batch
        cannot blow through the wall-clock budget."""
        self._batch_deadline = seconds

    def _take_batch_deadline(self) -> float | None:
        bd = self._batch_deadline
        self._batch_deadline = None
        return bd

    def _serial_with_deadline(self, workload, configs, batch_deadline):
        """Sequential evaluation honoring an armed batch deadline: configs
        that cannot start in time become red nodes, never silent skips.  At
        least one config is always evaluated so a batch makes progress."""
        if batch_deadline is None:
            return [self.evaluate(workload, c) for c in configs]
        end = time.monotonic() + batch_deadline
        out: list[Result] = []
        for c in configs:
            if out and time.monotonic() >= end:
                out.append(Result("exec_error",
                                  note="timeout (batch deadline)"))
                continue
            out.append(self.evaluate(workload, c))
        return out

    def _note_serial_fallback(self) -> None:
        self.faults["serial_fallbacks"] = (
            self.faults.get("serial_fallbacks", 0) + 1)
        if not self._warned_fallback:
            self._warned_fallback = True
            _log.warning(
                "%s: process pool unavailable/broken — measuring serially "
                "in-process (counted in faults['serial_fallbacks'])",
                self.name)

    def _ensure_pool(self) -> "SupervisedPool | None":
        """Create (once) the supervised worker pool, or ``None`` when it is
        impossible on this host (then the caller degrades to serial)."""
        if self._pool is not None:
            return self._pool
        if self._pool_broken:
            return None
        if self._pool_requires_pinning():
            # honest wall-clock timing needs one dedicated core per worker
            if not hasattr(os, "sched_setaffinity"):
                return None
            workers = min(self.process_workers, len(_usable_cores()))
        else:
            # deterministic backends run unpinned fine — don't clamp to the
            # core count (a 1-core host can still pipeline sleep/IO-bound
            # measurements across N workers)
            workers = self.process_workers
        if workers < 1:
            return None
        try:
            self._pool = SupervisedPool(
                self.name, self.worker_spec(), workers,
                deadline_s=self._pool_deadline(),
                mp_start_method=self.mp_start_method,
                breaker=self.breaker,
                faults=self.faults,
                serial_fallback=self.evaluate,
            )
            self._pool_lockdir = self._pool.lockdir
        except Exception:   # noqa: BLE001 — any startup failure → serial
            self.close()
            self._pool_broken = True
        return self._pool

    def submit_one(self, workload, config,
                   deadline_at: float | None = None):
        """Streaming dispatch: submit one measurement to the supervised pool
        and return its :class:`~concurrent.futures.Future`, or ``None`` when
        no pool is available (then the caller measures synchronously —
        results identical, just unpipelined)."""
        if getattr(self, "process_workers", 0) < 1:
            return None
        pool = self._ensure_pool()
        if pool is None:
            return None
        return pool.submit(workload, config, deadline_at=deadline_at)

    def pool_utilization(self) -> dict | None:
        """Utilization of the supervised pool, or ``None`` when no pool was
        ever used (so fault-free serial logs stay byte-identical)."""
        if self._pool is not None:
            self._last_pool_util = self._pool.utilization()
        return self._last_pool_util

    def close(self) -> None:
        """Shut down the worker pool and release the core-claim directory."""
        if self._pool is not None:
            self._last_pool_util = self._pool.utilization()
            self._pool.close()
            self._pool = None
        if self._pool_lockdir is not None:
            shutil.rmtree(self._pool_lockdir, ignore_errors=True)
            self._pool_lockdir = None

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.close()


@dataclass
class CostModelBackend(Backend):
    machine: Machine = XEON_8180M
    noise: float = 0.0          # multiplicative lognormal sigma (paper: "noise
                                # in the measurement"); 0 → deterministic
    seed: int = 0
    name: str = "costmodel"
    _rng: np.random.Generator | None = None

    def _measure(self, workload: Workload, nest: LoopNest) -> Result:
        t = estimate_time(nest, self.machine)
        if self.noise > 0:
            if self._rng is None:
                self._rng = np.random.default_rng(self.seed)
            t *= float(np.exp(self._rng.normal(0.0, self.noise)))
        return Result("ok", time_s=t)

    def worker_spec(self) -> dict:
        """Picklable constructor kwargs for a supervised-pool worker (used
        when a :class:`~repro.core.faults.FaultInjectingBackend` wraps this
        model inside a pool)."""
        return {"machine": self.machine, "noise": self.noise,
                "seed": self.seed}

    def store_scope(self) -> str:
        # Deterministic analytic model: host-independent.  Noisy runs are
        # scoped by (sigma, seed) so two noise settings never share samples.
        return (f"costmodel:{self.machine.name}"
                f":noise={self.noise}:seed={self.seed}")


@dataclass
class WallclockBackend(_SupervisedMeasureMixin, _ThreadedEvalMixin, Backend):
    """Real XLA:CPU execution at ``scale`` of the PolyBench extents.

    ``nest`` hints from the engine are ignored: the measured nest must be
    re-derived against the *scaled* extents, so each unique structure pays one
    full replay here (amortized by the engine's structural result cache).

    Timing honesty: the in-process thread pool is **forbidden** here
    (``max_workers > 1`` raises at construction) because concurrent timed
    runs share cores and skew each other.  Honest batching uses
    ``process_workers=N`` instead: a :class:`SupervisedPool` of ``spawn``
    workers (safe with an initialized JAX in the parent), each pinned to a
    dedicated CPU core, each supervised under a hard kill deadline
    (:meth:`hard_deadline` — ``deadline_s`` or a generous multiple of
    ``timeout_s``) so a hung measurement becomes a red node instead of
    blocking the run.  Falls back to sequential evaluation when pinning is
    unavailable (counted in ``faults``, warned once).  Call :meth:`close`
    (or use the backend as a context manager) to release the pool.
    """

    scale: float = 0.25
    reps: int = 3
    timeout_s: float = 20.0
    name: str = "wallclock"
    max_workers: int = 1        # thread path forbidden — see __post_init__
    process_workers: int = 0    # >=1 → supervised core-pinned worker pool
    mp_start_method: str = "spawn"
    deadline_s: float | None = None     # hard kill deadline override
    breaker: int = 3            # worker deaths before degrading to serial
    faults: dict = field(default_factory=dict, init=False, repr=False,
                         compare=False)
    _pool: object = field(default=None, init=False, repr=False, compare=False)
    _pool_lockdir: str | None = field(
        default=None, init=False, repr=False, compare=False)
    _pool_broken: bool = field(
        default=False, init=False, repr=False, compare=False)
    _batch_deadline: float | None = field(
        default=None, init=False, repr=False, compare=False)
    _warned_fallback: bool = field(
        default=False, init=False, repr=False, compare=False)

    def __post_init__(self) -> None:
        if self.max_workers > 1:
            raise ValueError(
                "WallclockBackend(max_workers>1): concurrent timed runs in "
                "one process contend for cores and skew every measurement. "
                "Use process_workers=N for the core-pinned process-pool "
                "path (honest parallel timing), or keep max_workers=1."
            )

    # -- supervised process-pool batching -------------------------------------

    def worker_spec(self) -> dict:
        """Picklable constructor kwargs from which a pool worker rebuilds
        this backend (``process_workers`` intentionally excluded — workers
        evaluate sequentially on their pinned core)."""
        return {"scale": self.scale, "reps": self.reps,
                "timeout_s": self.timeout_s}

    def hard_deadline(self) -> float:
        """Per-task supervised kill deadline.  Defaults to a generous
        multiple of the post-hoc ``timeout_s`` policy so the worker's own
        (byte-identical) timeout decision fires first and the SIGKILL only
        catches genuine hangs."""
        if self.deadline_s is not None:
            return self.deadline_s
        return self.timeout_s * (self.reps + 1) + 10.0

    def _pool_deadline(self) -> float:
        return self.hard_deadline()

    def _pool_requires_pinning(self) -> bool:
        return True             # unpinned parallel timing would be dishonest

    def evaluate_many(
        self,
        workload: Workload,
        configs: Sequence[Configuration],
        nests: Sequence[LoopNest | None] | None = None,
    ) -> list[Result]:
        # nest hints are ignored (re-derived against scaled extents; see
        # ``evaluate``), so they are simply not forwarded.
        batch_deadline = self._take_batch_deadline()
        if configs and self.process_workers >= 1:
            pool = self._ensure_pool()
            if pool is not None:
                out = pool.run(workload, list(configs),
                               batch_deadline_s=batch_deadline)
                if pool.broken:
                    # circuit breaker tripped: later batches run serially
                    # (recorded in faults["degraded"], never silent)
                    self.close()
                    self._pool_broken = True
                return out
            self._note_serial_fallback()
        return self._serial_with_deadline(workload, configs, batch_deadline)

    def store_scope(self) -> str:
        from .resultstore import host_fingerprint

        # Wall-clock times are a property of the measuring host *and* the
        # reduced problem scale; reps affect the min-of-N statistic, and the
        # timeout decides which configs are red.
        return (f"wallclock:scale={self.scale}:reps={self.reps}"
                f":timeout={self.timeout_s}@{host_fingerprint()}")

    def evaluate(
        self,
        workload: Workload,
        config: Configuration,
        nest: LoopNest | None = None,
    ) -> Result:
        w = workload.scaled(self.scale)
        try:
            nest = config.apply(w.nest())
        except TransformError as e:
            return Result("compile_error", note=str(e))
        try:
            check_legal(nest)
        except IllegalTransform as e:
            return Result("illegal", note=str(e))
        return self._measure(w, nest)

    def _measure(self, w: Workload, nest: LoopNest) -> Result:
        try:
            fn = codegen.build_xla(w, nest)
        except codegen.CodegenError as e:
            return Result("compile_error", note=str(e))
        args = {k: np.asarray(v) for k, v in w.make_args().items()}
        try:
            t0 = time.perf_counter()
            out = fn(args)
            out.block_until_ready()
            first = time.perf_counter() - t0   # includes compile
            if first > self.timeout_s:
                return Result("exec_error", note=f"timeout ({first:.1f}s)")
            times = []
            for _ in range(self.reps):
                t0 = time.perf_counter()
                fn(args).block_until_ready()
                times.append(time.perf_counter() - t0)
            return Result("ok", time_s=float(min(times)))
        except Exception as e:     # noqa: BLE001 — any XLA failure is a red node
            return Result("exec_error", note=f"{type(e).__name__}: {e}")


def _on_tpu() -> bool:
    """True when JAX's default backend is a TPU (initialises the backend)."""
    import jax

    return jax.default_backend() == "tpu"


def _is_kernel_workload(w) -> bool:
    """A workload is "any callable with a structure key": anything exposing
    ``build``/``vmem_bytes`` (e.g. :class:`~repro.core.kernelworkload.
    KernelWorkload`) supplies its own hand-written Pallas kernel and VMEM
    model instead of the einsum codegen path."""
    return callable(getattr(w, "build", None))


@dataclass
class PallasBackend(_SupervisedMeasureMixin, _ThreadedEvalMixin, Backend):
    """Builds the Pallas kernel, checks it against the jnp oracle, rejects
    VMEM-overflowing tiles, and scores with the TPU cost model.  The reported
    time is the cost model's, never a device time; it is deterministic, so
    batched verification can run on a thread pool safely.

    Two verification modes, never mixed in one store scope:

    * ``interpret=False`` (default) compiles every candidate with Mosaic and
      runs it at the workload's full extents.  It needs a TPU and raises
      without one.  A lowering or compile refusal is ``compile_error``; only
      a failed run or an oracle mismatch is ``exec_error``.
    * ``interpret=True`` runs the Pallas interpreter on any backend, at
      ``scale`` of the extents (``_retile_to``) so that it stays fast.

    Workloads exposing their own ``build``/``vmem_bytes`` (kernel workloads
    — the repo's hand-written Pallas kernels wrapped as tunables) take those
    in place of the einsum ``codegen`` path; everything else is identical.

    ``timeout_s`` arms a *hard* per-kernel deadline: with
    ``process_workers>=1`` verification runs inside a :class:`SupervisedPool`
    worker that is SIGKILLed (and respawned) when one interpret-mode
    verification hangs past the deadline — the kernel becomes an
    ``exec_error("timeout ...")`` red node.  Without workers the thread path
    cannot preempt, so ``timeout_s`` is only honored via the pool.  A TPU
    belongs to one process, so on a TPU host ``process_workers>=1`` is
    refused: the spawned workers could not open the chip."""

    machine: Machine = TPU_V5E
    scale: float = 0.05                 # interpret-mode verification only
    vmem_limit: int = 128 * 1024 * 1024
    verify: bool = True
    interpret: bool = False
    name: str = "pallas"
    max_workers: int = 4
    timeout_s: float | None = None      # hard kill deadline (needs workers)
    process_workers: int = 0            # >=1 → supervised worker pool
    mp_start_method: str = "spawn"
    breaker: int = 3
    faults: dict = field(default_factory=dict, init=False, repr=False,
                         compare=False)
    _pool: object = field(default=None, init=False, repr=False, compare=False)
    _pool_lockdir: str | None = field(
        default=None, init=False, repr=False, compare=False)
    _pool_broken: bool = field(
        default=False, init=False, repr=False, compare=False)
    _batch_deadline: float | None = field(
        default=None, init=False, repr=False, compare=False)
    _warned_fallback: bool = field(
        default=False, init=False, repr=False, compare=False)

    def __post_init__(self) -> None:
        if self.process_workers >= 1 and _on_tpu():
            raise ValueError(
                "PallasBackend(process_workers>=1) on a TPU host: a chip "
                "belongs to one process at a time, so spawned measurement "
                "workers cannot open it. Measure in the process that holds "
                "the chip (process_workers=0).")

    def worker_spec(self) -> dict:
        return {"machine": self.machine, "scale": self.scale,
                "vmem_limit": self.vmem_limit, "verify": self.verify,
                "interpret": self.interpret}

    def _pool_deadline(self) -> float | None:
        return self.timeout_s

    def evaluate_many(
        self,
        workload: Workload,
        configs: Sequence[Configuration],
        nests: Sequence[LoopNest | None] | None = None,
    ) -> list[Result]:
        batch_deadline = self._take_batch_deadline()
        if configs and self.process_workers >= 1:
            pool = self._ensure_pool()
            if pool is not None:
                out = pool.run(workload, list(configs),
                               batch_deadline_s=batch_deadline)
                if pool.broken:
                    self.close()
                    self._pool_broken = True
                return out
            self._note_serial_fallback()
        if batch_deadline is not None:
            # an armed batch deadline needs sequential dispatch to be able
            # to stop between kernels (nest hints are re-derived — results
            # are identical, see Backend.evaluate)
            return self._serial_with_deadline(workload, configs,
                                              batch_deadline)
        return _ThreadedEvalMixin.evaluate_many(self, workload, configs,
                                                nests)

    def store_scope(self) -> str:
        # Reported time is the deterministic TPU cost model → host-independent;
        # the verification mode (and, interpreted, its scale) and the vmem
        # limit decide which configs are red.
        mode = (f"interpret:scale={self.scale}" if self.interpret
                else "mosaic")
        return (f"pallas:{mode}:{self.machine.name}"
                f":vmem={self.vmem_limit}:verify={self.verify}")

    def _measure(self, workload: Workload, nest: LoopNest) -> Result:
        try:
            vmem = (workload.vmem_bytes(nest)
                    if _is_kernel_workload(workload)
                    else codegen.vmem_bytes(workload, nest))
            if vmem > self.vmem_limit:
                return Result(
                    "compile_error",
                    note=f"BlockSpec tiles exceed VMEM ({vmem} B)",
                )
        except codegen.CodegenError as e:
            return Result("compile_error", note=str(e))
        if self.verify:
            res = self._verify(workload, nest)
            if res is not None:
                return res
        return Result("ok", time_s=estimate_time(nest, self.machine))

    def _verify(self, workload: Workload, nest: LoopNest) -> Result | None:
        """Compile, run and check one candidate; a red :class:`Result`, or
        ``None`` when the kernel matches the oracle."""
        import jax

        if self.interpret:
            w = workload.scaled(self.scale)
            nest = _retile_to(nest, w)
        elif not _on_tpu():
            raise RuntimeError(
                f"PallasBackend: Mosaic compilation needs a TPU, and JAX's "
                f"default backend is {jax.default_backend()!r}. Pass "
                f"interpret=True to verify in the Pallas interpreter.")
        else:
            w = workload
        try:
            fn = (w.build(nest, interpret=self.interpret)
                  if _is_kernel_workload(w)
                  else codegen.build_pallas(w, nest,
                                            interpret=self.interpret))
        except codegen.CodegenError as e:
            return Result("compile_error", note=str(e))
        args = {k: jax.numpy.asarray(v) for k, v in w.make_args().items()}
        try:
            compiled = jax.jit(fn).lower(args).compile()
        except Exception as e:  # noqa: BLE001 — any refusal is a red node
            return Result("compile_error", note=f"{type(e).__name__}: {e}")
        try:
            got = np.asarray(compiled(args))
        except Exception as e:  # noqa: BLE001
            return Result("exec_error", note=f"{type(e).__name__}: {e}")
        with jax.default_matmul_precision("highest"):
            want = np.asarray(w.reference(args))
        if not np.allclose(got, want, rtol=2e-4, atol=2e-4):
            return Result(
                "exec_error",
                note=f"pallas/oracle mismatch: max err "
                f"{float(np.abs(got - want).max()):.3e}",
            )
        return None


# Built-in worker-backend builders (the "fault" kind registers itself on
# import of repro.core.faults — see build_worker_backend).
register_worker_backend("costmodel", CostModelBackend)
register_worker_backend("wallclock", WallclockBackend)
register_worker_backend("pallas", PallasBackend)


def _retile_to(nest: LoopNest, small: Workload) -> LoopNest:
    """Shrink a schedule's loop structure onto reduced extents so interpret-mode
    verification stays fast: tile sizes are clamped to the reduced extents."""
    from dataclasses import replace

    ext = dict(small.extents)
    new_loops = []
    per_var_seen: dict[str, int] = {}
    for l in nest.loops:
        e = ext.get(l.origin, l.trips)
        if l.is_point:
            trips = min(l.trips, max(4, e // 2))
        else:
            # floor trips: recompute from remaining extent
            pts = [x.trips for x in nest.loops if x.origin == l.origin and x.is_point]
            if pts:
                tile = min(pts[0], max(4, e // 2))
                trips = -(-e // tile)
            else:
                trips = e
        new_loops.append(replace(l, trips=trips))
    return replace(nest, loops=tuple(new_loops), extents=ext)
