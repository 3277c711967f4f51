"""Ask/tell tuning sessions — one measurement loop for every strategy.

The paper frames every search strategy as the same loop: derive children from
the tree-shaped search space (§III), pick which configuration to measure next,
observe the result (§IV-C).  Before this module, that loop was re-owned by
four monolithic ``run_*`` drivers which each re-threaded the same kwargs and
hard-wired measurement inline.  This module inverts the control flow:

* :class:`Strategy` — the ask/tell protocol (cf. Bayesian-optimization
  autotuners, arXiv:2010.08040; surrogate-informed MCTS, arXiv:2105.04555):
  :meth:`~Strategy.propose` returns up to ``n`` :class:`Proposal`\\ s, the
  session measures them as **one batch** through the shared
  :class:`~repro.core.evaluation.EvaluationEngine`, and
  :meth:`~Strategy.observe` feeds each logged
  :class:`~repro.core.autotuner.Experiment` back.  Strategies never measure;
  the session never searches.
* :func:`register_strategy` / :data:`STRATEGY_REGISTRY` — new strategies are
  ~50-line plugins (see :mod:`repro.core.acquisition` for the
  expected-improvement acquisition), not fifth and sixth driver forks.
* :class:`TuningSession` — owns the engine, batching, dedup, surrogate
  refits, result-store persistence, and budget accounting once;
  ``session.tune(workload, space, strategy="mcts", budget=...)`` returns the
  same :class:`~repro.core.autotuner.TuningLog` the legacy drivers did.  The
  legacy ``run_*`` functions survive as thin shims that are byte-identical
  to the pre-redesign drivers (A/B-tested against frozen copies).
* :class:`TuningSpec` — a declarative (dataclass ⇄ JSON) description of a
  whole tuning job: workload, space limits, backend, strategy, budget, store
  path.  One document round-trips through CI/fleet schedulers, and
  ``python -m repro.core.session spec.json`` runs it end to end.

Session/strategy contract
-------------------------
* A :class:`Strategy` instance drives **one** run; the registry constructs a
  fresh instance per :meth:`TuningSession.tune` call when given a name/class.
* ``propose(n)`` returns at most ``n`` proposals; every returned proposal is
  evaluated and logged (in order), so a strategy may pre-assign experiment
  numbers (``len(log)`` at propose time + offset) for parent attribution.
* An empty ``propose`` is allowed while :attr:`~Strategy.finished` is False —
  the session just re-checks budgets and asks again (e.g. greedy popping a
  fully-deduped parent) — but the strategy must guarantee progress toward
  ``finished``, or the loop would spin.
* The session evaluates each proposal batch with
  :meth:`EvaluationEngine.evaluate_many`: intra-batch structural duplicates
  are measured once, results replay from the structural cache and the
  persistent store exactly as they did inline in the legacy drivers.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import pickle
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Sequence

from .autotuner import Experiment, NoSuccessfulExperiment, TuningLog
from .evaluation import EvaluationEngine
from .faults import FaultInjectingBackend
from .measure import Backend, CostModelBackend, PallasBackend, WallclockBackend
from .searchspace import Configuration, SearchSpace
from .kernelworkload import KERNEL_WORKLOAD_BUILDERS, kernel_workload
from .workloads import PAPER_WORKLOADS, Workload, matmul_workload

_log = logging.getLogger("repro.core.session")

#: Bump when the checkpoint payload layout changes — a mismatched sidecar is
#: rejected (resume from a stale format would corrupt the run silently).
#: v2: MCTS snapshots carry a pending-descent dict and per-node pending
#: counters (async virtual loss) instead of a single optional tuple.
CHECKPOINT_VERSION = 2

__all__ = [
    "Proposal",
    "Strategy",
    "STRATEGY_REGISTRY",
    "TuningSession",
    "TuningSpec",
    "register_strategy",
    "resolve_strategy",
]


# ---------------------------------------------------------------------------
# The ask/tell protocol
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Proposal:
    """One configuration a strategy asks the session to measure.

    ``parent`` is the experiment number the logged result should attach to
    (None for the baseline) — parent edges are strategy knowledge (greedy's
    popped heap node, MCTS's expansion node), so they travel with the ask.
    ``prepped`` optionally carries the (nest-or-error, canonical key) pair
    from :meth:`EvaluationEngine.prep`/:meth:`~EvaluationEngine.
    select_prepped`: a strategy that derived the structure while selecting
    attaches it so the session's batched evaluation skips the re-derivation
    (measurable on the greedy hot loop; results are identical either way).
    """

    config: Configuration
    parent: int | None = None
    prepped: tuple | None = field(default=None, compare=False)


class Strategy:
    """Base class of the ask/tell protocol.

    Subclasses implement :meth:`propose` / :meth:`observe` / :attr:`finished`
    and are registered by name via :func:`register_strategy`.  The session
    :meth:`bind`\\ s the strategy to the run's engine/space/workload before
    the first ``propose`` — strategies consult the engine for dedup
    (``claim``), ordering (``order_children``/``select``), stored
    measurements (``peek``) and surrogate scores, but never measure.
    """

    engine: EvaluationEngine
    space: SearchSpace
    workload: Workload

    def bind(self, engine: EvaluationEngine, space: SearchSpace,
             workload: Workload) -> None:
        self.engine = engine
        self.space = space
        self.workload = workload
        self.on_bound()

    def on_bound(self) -> None:
        """Hook for derived state that needs the bound engine (e.g. MCTS
        checks ``engine.stats.preloaded`` to enable warm ordering)."""

    def propose(self, n: int) -> Sequence[Proposal]:
        """Ask: up to ``n`` configurations to measure next (the session
        evaluates them as one batch and logs every one, in order)."""
        raise NotImplementedError

    def observe(self, exp: Experiment) -> None:
        """Tell: one logged experiment (config, result, number, parent)."""
        raise NotImplementedError

    @property
    def finished(self) -> bool:
        """True once the strategy has nothing left to propose."""
        return False

    def finalize(self, log: TuningLog) -> None:
        """Hook called after the run with ``log.cache`` populated —
        strategies append their own counters here (e.g. MCTS transposition
        stats)."""

    def snapshot(self) -> dict:
        """Picklable strategy state for session checkpoints: every instance
        attribute except the bound engine/space/workload (those are rebuilt
        by :meth:`bind` on resume).  Built-in strategies keep all search
        state (heaps, MCTS tree, RNGs) in plain picklable attributes, so
        this default suffices; a subclass holding unpicklable state must
        override both :meth:`snapshot` and :meth:`restore`."""
        return {k: v for k, v in vars(self).items()
                if k not in ("engine", "space", "workload")}

    def restore(self, state: dict) -> None:
        """Inverse of :meth:`snapshot` — called *after* :meth:`bind` on
        resume, so restored state wins over anything :meth:`on_bound`
        derived."""
        vars(self).update(state)


# ---------------------------------------------------------------------------
# Strategy registry
# ---------------------------------------------------------------------------

STRATEGY_REGISTRY: dict[str, type[Strategy]] = {}


def register_strategy(name: str) -> Callable[[type[Strategy]], type[Strategy]]:
    """Class decorator registering a :class:`Strategy` under ``name`` so
    ``TuningSession.tune(..., strategy=name)`` and :class:`TuningSpec`
    documents can resolve it.  Re-registering a name overwrites (lets tests
    and downstream plugins shadow built-ins deliberately)."""

    def deco(cls: type[Strategy]) -> type[Strategy]:
        cls.strategy_name = name
        STRATEGY_REGISTRY[name] = cls
        return cls

    return deco


def _ensure_builtin_strategies() -> None:
    # Built-in strategies live in sibling modules that import *this* module
    # for the base class — registration happens on their import, which must
    # therefore be lazy here to avoid a cycle.
    from . import acquisition, strategies  # noqa: F401


def resolve_strategy(spec, **kwargs) -> Strategy:
    """Resolve a strategy *name*, *class*, or *instance* to a bound-ready
    instance.  ``kwargs`` are constructor arguments (rejected for instances —
    an already-constructed strategy carries its own configuration)."""
    if isinstance(spec, Strategy):
        if kwargs:
            raise TypeError(
                f"strategy kwargs {sorted(kwargs)} cannot be applied to an "
                f"already-constructed {type(spec).__name__} instance")
        return spec
    if isinstance(spec, type) and issubclass(spec, Strategy):
        return spec(**kwargs)
    if isinstance(spec, str):
        _ensure_builtin_strategies()
        cls = STRATEGY_REGISTRY.get(spec)
        if cls is None:
            raise ValueError(
                f"unknown strategy {spec!r} "
                f"(registered: {', '.join(sorted(STRATEGY_REGISTRY))})")
        return cls(**kwargs)
    raise TypeError(f"strategy must be a name, Strategy subclass or "
                    f"instance, got {type(spec).__name__}")


# ---------------------------------------------------------------------------
# The session facade
# ---------------------------------------------------------------------------


class TuningSession:
    """Owns measurement for ask/tell strategies — the one public entry point.

    ``backend``/``store``/``surrogate``/``cache`` configure the
    :class:`~repro.core.evaluation.EvaluationEngine` constructed per
    :meth:`tune` call (semantics identical to the legacy drivers' kwargs:
    ``store`` attaches the persistent :class:`~repro.core.resultstore.
    ResultStore` for cross-run warm starts — a path, a ``jsonl://`` /
    ``sqlite://`` URI, an instance, or ``False`` to opt out of the
    ``CC_RESULT_STORE`` ambient default; ``surrogate`` is
    ``"analytic" | "learned" | Surrogate | None``).  ``surrogate_scope``
    relaxes the learned surrogate's warm-start training pool
    (``"exact" | "same_backend" | "cross_workload"`` — see
    :meth:`ResultStore.query`; replay is always exact) and
    ``surrogate_peers`` names extra workloads whose pooled records should be
    featurizable.  One session may run many tunes (different
    workloads/spaces/strategies) against the same backend; each tune gets a
    fresh engine unless one is injected.
    """

    def __init__(
        self,
        backend: Backend,
        *,
        store=None,
        surrogate=None,
        cache: bool = True,
        surrogate_scope: str = "exact",
        surrogate_peers: Sequence[Workload] = (),
        retry=None,
        static_analysis: bool = False,
    ):
        self.backend = backend
        self.store = store
        self.surrogate = surrogate
        self.cache = cache
        self.surrogate_scope = surrogate_scope
        self.surrogate_peers = tuple(surrogate_peers)
        # RetryPolicy | dict | None — forwarded to the engine (see
        # repro.core.faults.RetryPolicy for the retry/quarantine semantics)
        self.retry = retry
        # opt-in static red-node prediction (repro.analysis): statically
        # infeasible schedules short-circuit without backend dispatch
        self.static_analysis = static_analysis

    def tune(
        self,
        workload: Workload,
        space: SearchSpace,
        strategy="greedy",
        budget: int = 400,
        *,
        max_seconds: float | None = None,
        on_experiment: Callable[[Experiment], None] | None = None,
        engine: EvaluationEngine | None = None,
        checkpoint: "str | os.PathLike | None" = None,
        checkpoint_every: int = 25,
        resume: bool = False,
        async_workers: int = 0,
        **strategy_kwargs,
    ) -> TuningLog:
        """Run one ask/tell tuning loop and return its :class:`TuningLog`.

        ``strategy`` is a registry name (``"greedy" | "mcts" | "beam" |
        "random" | "ei" | ...``), a :class:`Strategy` subclass, or an
        instance; ``strategy_kwargs`` go to the constructor (``seed=``,
        ``width=``, ``c_explore=``, ...).  ``engine`` injects an externally
        constructed engine (it carries dedup/cache state — the
        :class:`~repro.core.autotuner.Autotuner` compatibility path uses
        this); otherwise one is built from the session's configuration.

        ``max_seconds`` is a hard wall-clock bound: the loop predicts how
        many more experiments fit from the observed per-experiment pace and
        clips each ask's ``room`` accordingly, and backends exposing
        ``set_batch_deadline`` get the remaining seconds as a per-batch
        measurement deadline — configs a batch cannot start in time come
        back as ``exec_error`` red nodes instead of overshooting.  (The
        baseline experiment is still always measured.)

        ``checkpoint`` names a crash-safe sidecar file: every
        ``checkpoint_every`` experiments the full session state (log,
        strategy state, engine caches/counters, elapsed wall clock) is
        pickled to it atomically (tmp + fsync + rename).  ``resume=True``
        loads it and continues the run mid-loop — a killed session replayed
        with the same spec reaches the byte-identical best; a missing
        sidecar logs a warning and starts fresh, so ``resume=True`` is safe
        as an unconditional default in supervisors.

        ``async_workers=N`` (N >= 1) switches to the **pipelined** loop:
        proposals are submitted as streaming measurements
        (:meth:`EvaluationEngine.submit_prepped` over the backend's
        supervised pool) and the strategy keeps proposing speculatively
        against in-flight results — up to ~2·N measurements stay in flight
        so all N pool workers remain busy while the strategy thinks and the
        surrogate refits.  Results are observed as they land (strategies
        tolerate out-of-order observes; MCTS applies virtual loss to pending
        descents), experiments are logged under their submission number, and
        checkpoints land only at quiescent points (everything in flight
        drained), preserving the ``--resume`` guarantee.  ``async_workers=0``
        (the default) is the synchronous loop, byte-identical to before the
        async mode existed; a backend without a pool degrades the async loop
        to synchronous completion — identical results, no pipelining.
        """
        strat = resolve_strategy(strategy, **strategy_kwargs)
        engine = engine or EvaluationEngine(
            workload, space, self.backend,
            cache=self.cache, surrogate=self.surrogate, store=self.store,
            surrogate_scope=self.surrogate_scope,
            surrogate_peers=self.surrogate_peers,
            retry=self.retry,
            static_analysis=self.static_analysis,
        )
        log = TuningLog(workload=workload.name, backend=self.backend.name)

        ck = None
        if resume:
            if not checkpoint:
                raise ValueError("tune(resume=True) requires checkpoint=")
            ck = self._load_checkpoint(checkpoint, workload, strat)
        if ck is not None:
            # Engine state restores BEFORE bind (on_bound consults engine
            # counters, e.g. MCTS warm ordering); strategy state AFTER bind
            # (restored search state beats anything on_bound derived).
            engine.restore(ck["engine_state"])
            strat.bind(engine, space, workload)
            strat.restore(ck["strategy_state"])
            log.experiments = list(ck["experiments"])
            t_start = time.perf_counter() - ck["elapsed_s"]
            if ck["finished"]:
                # the run completed before the restart: return its log
                # verbatim (the saved cache includes backend fault counters
                # a fresh backend could not reproduce)
                log.cache = ck["cache"]
                return log
        else:
            strat.bind(engine, space, workload)
            t_start = time.perf_counter()
        last_ckpt = len(log.experiments)

        if async_workers:
            return self._tune_async(
                strat, engine, log, workload, budget, max_seconds,
                on_experiment, checkpoint, checkpoint_every, t_start,
                last_ckpt, int(async_workers))

        while not strat.finished:
            # The baseline is exempt from the experiment budget: every legacy
            # driver recorded and measured experiment 0 even under budget<=0
            # ("executed too, since it might be the fastest configuration",
            # §IV-C), so the first ask always gets room for one proposal.
            if log.experiments and len(log.experiments) >= budget:
                break
            if (max_seconds is not None
                    and time.perf_counter() - t_start > max_seconds):
                break
            room = budget - len(log.experiments)
            if not log.experiments:
                room = max(room, 1)
            if max_seconds is not None and log.experiments:
                # Pace-based clip: never ask for more experiments than the
                # remaining wall clock is observed to afford, and hand the
                # remaining seconds down as the batch measurement deadline.
                elapsed = time.perf_counter() - t_start
                remaining = max_seconds - elapsed
                if remaining <= 0:
                    break
                per = elapsed / len(log.experiments)
                if per > 0:
                    room = min(room, max(1, int(remaining / per)))
                set_bd = getattr(self.backend, "set_batch_deadline", None)
                if set_bd is not None:
                    set_bd(remaining)
            proposals = list(strat.propose(room))
            if not proposals:
                continue    # e.g. greedy popped a fully-deduped parent
            results = engine.evaluate_prepped(
                [(p.config, *(p.prepped if p.prepped is not None
                              else engine.prep(p.config)))
                 for p in proposals])
            for prop, res in zip(proposals, results):
                exp = Experiment(number=len(log.experiments),
                                 config=prop.config, result=res,
                                 parent=prop.parent)
                log.experiments.append(exp)
                if on_experiment:
                    on_experiment(exp)
                strat.observe(exp)
            if (checkpoint
                    and len(log.experiments) - last_ckpt >= checkpoint_every):
                self._save_checkpoint(checkpoint, workload, strat, engine,
                                      log, t_start, finished=False)
                last_ckpt = len(log.experiments)
        log.cache = engine.stats_dict()
        strat.finalize(log)
        if checkpoint:
            self._save_checkpoint(checkpoint, workload, strat, engine, log,
                                  t_start, finished=True)
        return log

    def _tune_async(self, strat: Strategy, engine: EvaluationEngine,
                    log: TuningLog, workload: Workload, budget: int,
                    max_seconds: "float | None",
                    on_experiment: "Callable[[Experiment], None] | None",
                    checkpoint, checkpoint_every: int, t_start: float,
                    last_ckpt: int, workers: int) -> TuningLog:
        """The pipelined ask/tell loop (``tune(async_workers=N)``).

        Invariants vs the synchronous loop: every proposal is submitted
        under a contiguous submission number and logged exactly once; the
        budget caps *submissions* (at quiescence submissions == logged
        experiments, so the budget semantics match); ``max_seconds``
        clipping counts submitted-but-unobserved measurements so the
        pipeline cannot overshoot; checkpoints and the finished-log tail
        run only at quiescent points.  With an instant (pool-less) backend
        every submission completes synchronously and the inner submit loop
        yields to observation first, so the trajectory is identical to the
        synchronous session — the pipelining only reorders genuinely
        concurrent measurements."""
        lookahead = max(workers + 1, 2 * workers)
        inflight: "list[tuple[int, Proposal, object]]" = []
        submitted = len(log.experiments)
        stop = False

        def drain_done() -> int:
            done = [t for t in inflight if t[2].done]
            if not done:
                return 0
            inflight[:] = [t for t in inflight if not t[2].done]
            for num, prop, h in done:
                exp = Experiment(number=num, config=prop.config,
                                 result=h.result, parent=prop.parent)
                log.experiments.append(exp)
                if on_experiment:
                    on_experiment(exp)
                strat.observe(exp)
            return len(done)

        while True:
            if not inflight:
                # quiescent point: the log is complete, budgets are
                # re-checked exactly like the sync loop, checkpoints are safe
                log.experiments.sort(key=lambda e: e.number)
                if strat.finished or stop:
                    break
                if log.experiments and submitted >= budget:
                    break
                if (max_seconds is not None
                        and time.perf_counter() - t_start > max_seconds):
                    break
                if (checkpoint and
                        len(log.experiments) - last_ckpt >= checkpoint_every):
                    self._save_checkpoint(checkpoint, workload, strat,
                                          engine, log, t_start,
                                          finished=False)
                    last_ckpt = len(log.experiments)
            made = 0
            if not stop:
                room = budget - submitted
                if not log.experiments and not inflight:
                    # the baseline is exempt from the budget (see tune())
                    room = max(room, 1)
                deadline_at = None
                if max_seconds is not None and log.experiments:
                    elapsed = time.perf_counter() - t_start
                    remaining = max_seconds - elapsed
                    if remaining <= 0:
                        stop, room = True, 0
                    else:
                        deadline_at = time.monotonic() + remaining
                        per = elapsed / len(log.experiments)
                        if per > 0:
                            # in-flight measurements already claim a share
                            # of the remaining wall clock — count them so
                            # the pipelined loop cannot overshoot
                            afford = int(remaining / per) - len(inflight)
                            floor = 0 if inflight else 1
                            room = min(room, max(floor, afford))
                while room > 0 and len(inflight) < lookahead:
                    props = list(strat.propose(room))
                    if not props:
                        break
                    for p in props:
                        nest, key = (p.prepped if p.prepped is not None
                                     else engine.prep(p.config))
                        h = engine.submit_prepped(p.config, nest, key,
                                                  deadline_at=deadline_at)
                        inflight.append((submitted, p, h))
                        submitted += 1
                        made += 1
                        room -= 1
                    if any(t[2].done for t in inflight):
                        # observe what already landed before speculating
                        # further — this is what degrades an instant
                        # backend to the synchronous trajectory
                        break
            if inflight:
                engine.settle([t[2] for t in inflight], block=(made == 0))
                drain_done()
            elif made == 0:
                if stop:
                    break
                # nothing proposed, nothing in flight, not finished: the
                # strategy promises progress (same contract as the sync
                # loop) — re-check budgets and ask again
                continue

        log.experiments.sort(key=lambda e: e.number)
        log.cache = engine.stats_dict()
        strat.finalize(log)
        if checkpoint:
            self._save_checkpoint(checkpoint, workload, strat, engine, log,
                                  t_start, finished=True)
        return log

    # -- crash-safe checkpointing --------------------------------------------

    @staticmethod
    def _strategy_name(strat: Strategy) -> str:
        return getattr(strat, "strategy_name", type(strat).__name__)

    def _save_checkpoint(self, path, workload: Workload, strat: Strategy,
                         engine: EvaluationEngine, log: TuningLog,
                         t_start: float, *, finished: bool) -> None:
        payload = {
            "version": CHECKPOINT_VERSION,
            "workload": workload.name,
            "backend": self.backend.name,
            "strategy": self._strategy_name(strat),
            "finished": finished,
            "elapsed_s": time.perf_counter() - t_start,
            "cache": log.cache,     # populated only on the finished save
            "experiments": list(log.experiments),
            "strategy_state": strat.snapshot(),
            "engine_state": engine.snapshot(),
        }
        # Atomic sidecar: a crash mid-write must leave the previous
        # checkpoint intact, so pickle to a sibling tmp, fsync, rename.
        path = os.fspath(path)
        tmp = f"{path}.tmp"
        with open(tmp, "wb") as f:
            pickle.dump(payload, f, protocol=pickle.HIGHEST_PROTOCOL)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def _load_checkpoint(self, path, workload: Workload,
                         strat: Strategy) -> "dict | None":
        path = os.fspath(path)
        try:
            with open(path, "rb") as f:
                ck = pickle.load(f)
        except FileNotFoundError:
            _log.warning("checkpoint %s not found — starting fresh", path)
            return None
        except Exception as e:     # noqa: BLE001 — truncated/corrupt pickle
            raise ValueError(
                f"checkpoint {path!r} is unreadable "
                f"({type(e).__name__}: {e}); delete it to start fresh"
            ) from e
        if ck.get("version") != CHECKPOINT_VERSION:
            raise ValueError(
                f"checkpoint {path!r} has version {ck.get('version')!r}, "
                f"expected {CHECKPOINT_VERSION}; delete it to start fresh")
        want = {"workload": workload.name, "backend": self.backend.name,
                "strategy": self._strategy_name(strat)}
        got = {k: ck.get(k) for k in want}
        if got != want:
            raise ValueError(
                f"checkpoint {path!r} belongs to a different run "
                f"({got} != {want}); delete it or fix the spec")
        return ck


# ---------------------------------------------------------------------------
# Declarative tuning jobs (dataclass ⇄ JSON)
# ---------------------------------------------------------------------------

_BACKENDS = {
    "costmodel": CostModelBackend,
    "wallclock": WallclockBackend,
    "pallas": PallasBackend,
    "fault": FaultInjectingBackend,
}

# JSON arrays decode as lists; these SearchSpace/backend fields want tuples.
_TUPLE_SPACE_FIELDS = ("tile_sizes", "unroll_factors")


@dataclass
class TuningSpec:
    """A whole tuning job as one serializable document.

    ``workload`` names a :data:`~repro.core.workloads.PAPER_WORKLOADS` entry,
    ``"matmul"`` (with ``workload_args`` = m/n/k/... for
    :func:`~repro.core.workloads.matmul_workload`), or one of the repo's own
    Pallas kernels — ``"attention"`` / ``"ssd"`` via
    :func:`~repro.core.kernelworkload.kernel_workload`, with
    ``workload_args`` = the builder kwargs; ``workload_args`` may
    also carry ``scale`` to pre-scale extents.  ``space_args`` are
    :class:`SearchSpace` kwargs (sans ``root``), ``backend_args`` the
    backend constructor's, ``strategy_args`` the strategy constructor's.
    ``store`` is a result-store target for the cross-run warm start — a
    path or a ``jsonl://`` / ``sqlite://`` URI (backend resolved by scheme
    or suffix), JSON ``false`` for an explicit opt-out that beats the
    ``CC_RESULT_STORE`` ambient default, ``null`` to defer to it.
    ``surrogate_scope`` is the learned surrogate's training-pool relaxation
    (``"exact"`` / ``"same_backend"`` / ``"cross_workload"``), and
    ``surrogate_peers`` names the extra workloads whose pooled records must
    be featurizable — each entry a ``{"workload": name, "workload_args":
    {...}}`` object resolved exactly like the spec's own workload (paper
    workloads are always recognized; peers matter for scaled/matmul
    fingerprints).

    Fault tolerance: ``retry`` is a :class:`~repro.core.faults.RetryPolicy`
    as a JSON object (``{"max_attempts": 3, "backoff_s": 0.05,
    "backoff_factor": 2.0, "jitter": 0.1, "quarantine_after": 3, "seed":
    0}`` — all fields optional), ``null`` to disable retries.
    ``checkpoint`` names the crash-safe session sidecar written atomically
    every ``checkpoint_every`` experiments; ``python -m repro.core.session
    spec.json --resume`` continues a killed run from it.
    ``async_workers`` (default 0) switches :meth:`TuningSession.tune` to
    the pipelined loop with that many measurements in flight — see
    :meth:`TuningSession.tune` for the semantics.  The ``"fault"``
    backend (fault-injection harness) takes an ``inner`` field in its
    ``backend_args`` — a nested ``{"backend": ..., "backend_args": {...}}``
    object resolved recursively.

    Round-trips losslessly through :meth:`to_json`/:meth:`from_json`, and
    ``python -m repro.core.session spec.json`` executes it.
    """

    workload: str = "gemm"
    workload_args: dict = field(default_factory=dict)
    strategy: str = "greedy"
    strategy_args: dict = field(default_factory=dict)
    budget: int = 400
    backend: str = "costmodel"
    backend_args: dict = field(default_factory=dict)
    space_args: dict = field(default_factory=dict)
    surrogate: str | None = None
    store: str | bool | None = None
    cache: bool = True
    surrogate_scope: str = "exact"
    surrogate_peers: list = field(default_factory=list)
    retry: dict | None = None
    checkpoint: str | None = None
    checkpoint_every: int = 25
    async_workers: int = 0
    # opt-in static red-node prediction (repro.analysis): statically
    # infeasible schedules become instant red nodes, zero worker dispatch
    static_analysis: bool = False

    # -- serialization -------------------------------------------------------

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TuningSpec":
        if not isinstance(d, dict):
            raise ValueError(f"TuningSpec document must be a JSON object, "
                             f"got {type(d).__name__}")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"unknown TuningSpec field(s) {sorted(unknown)} "
                f"(known: {sorted(known)})")
        return cls(**d)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=1)

    @classmethod
    def from_json(cls, s: str) -> "TuningSpec":
        return cls.from_dict(json.loads(s))

    def save(self, path: str | os.PathLike) -> None:
        with open(path, "w", encoding="utf-8") as f:
            f.write(self.to_json() + "\n")

    @classmethod
    def load(cls, path: str | os.PathLike) -> "TuningSpec":
        with open(path, "r", encoding="utf-8") as f:
            return cls.from_json(f.read())

    # -- resolution ----------------------------------------------------------

    @staticmethod
    def _resolve_workload(name: str, workload_args: dict) -> Workload:
        args = dict(workload_args)
        scale = args.pop("scale", None)
        if name == "matmul":
            args.setdefault("name", "matmul")
            w = matmul_workload(**args)
        elif name in KERNEL_WORKLOAD_BUILDERS:
            # The repo's own Pallas kernels as tunables ("attention", "ssd");
            # workload_args become the builder kwargs (head counts, seq
            # lengths, causal flag, ...).
            w = kernel_workload(name, **args)
        else:
            if args:
                raise ValueError(
                    f"workload_args {sorted(args)} are only valid for "
                    f"workload='matmul' or kernel workloads "
                    f"({', '.join(sorted(KERNEL_WORKLOAD_BUILDERS))}) "
                    f"(besides 'scale')")
            w = PAPER_WORKLOADS.get(name)
            if w is None:
                raise ValueError(
                    f"unknown workload {name!r} (known: "
                    f"{', '.join(sorted(PAPER_WORKLOADS))}, matmul, "
                    f"{', '.join(sorted(KERNEL_WORKLOAD_BUILDERS))})")
        return w.scaled(scale) if scale is not None else w

    def build_workload(self) -> Workload:
        return self._resolve_workload(self.workload, self.workload_args)

    def build_peers(self) -> list[Workload]:
        """The ``surrogate_peers`` entries as workloads (each resolved
        exactly like the spec's own workload)."""
        peers = []
        for i, entry in enumerate(self.surrogate_peers):
            if not isinstance(entry, dict) or "workload" not in entry:
                raise ValueError(
                    f"surrogate_peers[{i}] must be an object with a "
                    f"'workload' field (and optional 'workload_args'), "
                    f"got {entry!r}")
            unknown = set(entry) - {"workload", "workload_args"}
            if unknown:
                raise ValueError(
                    f"surrogate_peers[{i}]: unknown field(s) "
                    f"{sorted(unknown)}")
            peers.append(self._resolve_workload(
                entry["workload"], entry.get("workload_args", {})))
        return peers

    def build_space(self, workload: Workload) -> SearchSpace:
        args = dict(self.space_args)
        for f in _TUPLE_SPACE_FIELDS:
            if f in args:
                args[f] = tuple(args[f])
        return SearchSpace(root=workload.nest(), **args)

    @staticmethod
    def _resolve_backend(name: str, backend_args: dict) -> Backend:
        cls = _BACKENDS.get(name)
        if cls is None:
            raise ValueError(f"unknown backend {name!r} "
                             f"(known: {', '.join(sorted(_BACKENDS))})")
        args = dict(backend_args)
        if name == "fault":
            # The fault injector wraps a real backend: its ``inner`` is a
            # nested {"backend": ..., "backend_args": {...}} spec fragment,
            # resolved recursively (fault-over-fault composes).
            inner = args.pop("inner", None)
            if not isinstance(inner, dict) or "backend" not in inner:
                raise ValueError(
                    "backend 'fault' requires backend_args.inner = "
                    "{'backend': <name>, 'backend_args': {...}}")
            unknown = set(inner) - {"backend", "backend_args"}
            if unknown:
                raise ValueError(
                    f"backend_args.inner: unknown field(s) {sorted(unknown)}")
            args["inner"] = TuningSpec._resolve_backend(
                inner["backend"], inner.get("backend_args", {}))
        return cls(**args)

    def build_backend(self) -> Backend:
        return self._resolve_backend(self.backend, self.backend_args)

    def run(self, on_experiment: Callable[[Experiment], None] | None = None,
            *, resume: bool = False) -> TuningLog:
        """Execute the job end to end and return the :class:`TuningLog`."""
        workload = self.build_workload()
        session = TuningSession(
            self.build_backend(),
            store=self.store, surrogate=self.surrogate, cache=self.cache,
            surrogate_scope=self.surrogate_scope,
            surrogate_peers=self.build_peers(),
            retry=self.retry,
            static_analysis=self.static_analysis,
        )
        return session.tune(
            workload, self.build_space(workload),
            strategy=self.strategy, budget=self.budget,
            on_experiment=on_experiment,
            checkpoint=self.checkpoint,
            checkpoint_every=self.checkpoint_every,
            resume=resume,
            async_workers=self.async_workers,
            **self.strategy_args,
        )


# ---------------------------------------------------------------------------
# CLI entry point: python -m repro.core.session spec.json
# ---------------------------------------------------------------------------


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.core.session",
        description="Run a declarative TuningSpec JSON document end to end.")
    ap.add_argument("spec", help="path to a TuningSpec JSON document")
    ap.add_argument("--out", metavar="LOG.json", default=None,
                    help="write the full TuningLog JSON here")
    ap.add_argument("--budget", type=int, default=None,
                    help="override the spec's experiment budget")
    ap.add_argument("--store", default=None,
                    help="override the spec's result-store target (path or "
                         "jsonl://... / sqlite://... URI; an empty string "
                         "explicitly disables the store, beating "
                         "CC_RESULT_STORE)")
    ap.add_argument("--checkpoint", metavar="CKPT.pkl", default=None,
                    help="override the spec's crash-safe checkpoint sidecar")
    ap.add_argument("--async-workers", type=int, default=None,
                    metavar="N", dest="async_workers",
                    help="override the spec's async_workers (pipelined "
                         "session with N measurements in flight; 0 = the "
                         "synchronous loop)")
    ap.add_argument("--static-analysis", action="store_true",
                    dest="static_analysis",
                    help="override the spec's static_analysis to on: "
                         "statically-infeasible schedules become instant "
                         "red nodes with zero worker dispatch "
                         "(repro.analysis; lint the spec first with "
                         "python -m repro.analysis.lint)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the checkpoint sidecar (missing file "
                         "starts fresh; a mismatched one is an error)")
    ap.add_argument("--stream", action="store_true",
                    help="emit one NDJSON line per experiment on stdout as "
                         "it completes (the job-level streaming hook the "
                         "fleet follows; implies --quiet for the summary "
                         "line, which moves to a final {\"event\": \"done\"} "
                         "record)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the per-run summary line")
    args = ap.parse_args(argv)

    from repro import compile_cache
    compile_cache.enable()

    try:
        spec = TuningSpec.load(args.spec)
    except (OSError, ValueError, TypeError) as e:
        print(f"error: cannot load spec {args.spec!r}: {e}", file=sys.stderr)
        return 2
    if args.budget is not None:
        spec.budget = args.budget
    if args.store is not None:
        spec.store = args.store
    if args.checkpoint is not None:
        spec.checkpoint = args.checkpoint
    if args.async_workers is not None:
        spec.async_workers = args.async_workers
    if args.static_analysis:
        spec.static_analysis = True

    on_experiment = None
    if args.stream:
        def on_experiment(exp: Experiment) -> None:
            # NDJSON event stream: one self-describing line per experiment,
            # flushed immediately so a follower (pipe, fleet dispatcher)
            # sees results as they land, not at process exit
            print(json.dumps({"event": "experiment", **exp.to_dict()},
                             separators=(",", ":")), flush=True)

    try:
        log = spec.run(on_experiment, resume=args.resume)
    except (ValueError, TypeError) as e:
        print(f"error: spec {args.spec!r} failed to resolve: {e}",
              file=sys.stderr)
        return 2

    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(log.to_json())
    try:
        best = log.best()
        summary = (f"best time_s={best.result.time_s:.6g} "
                   f"at experiment #{best.number}")
        best_dict = {"time_s": best.result.time_s, "number": best.number}
        rc = 0
    except NoSuccessfulExperiment as e:
        summary = f"FAILED: {e}"
        best_dict = None
        rc = 1
    if args.stream:
        print(json.dumps({"event": "done", "workload": log.workload,
                          "backend": log.backend, "strategy": spec.strategy,
                          "experiments": len(log.experiments),
                          "best": best_dict},
                         separators=(",", ":")), flush=True)
    elif not args.quiet:
        print(f"{log.workload} [{spec.strategy} on {log.backend}] "
              f"{len(log.experiments)} experiments: {summary}")
    return rc


if __name__ == "__main__":
    # Under ``python -m repro.core.session`` runpy executes a *second* copy
    # of this module (the package __init__ already imported the canonical
    # one, whose registry the built-in strategies populated).  Delegate to
    # the canonical module so there is exactly one STRATEGY_REGISTRY.
    from repro.core.session import main as _canonical_main

    sys.exit(_canonical_main())
