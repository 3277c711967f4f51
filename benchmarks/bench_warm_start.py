"""Warm-start gates for the persistent measurement subsystem.

Two acceptance gates (summary saved to ``results/warm_start.json``):

1. **Cross-process wallclock warm start** — for gemm and covariance, a cold
   greedy tuning run on the real :class:`WallclockBackend` (XLA compile + run
   + time per experiment) populates a fresh :class:`ResultStore`; a second run
   *in a fresh process* preloads it.  Gate: the warm run achieves **≥ 5×**
   the cold run's experiments/sec with a **byte-identical** best
   configuration.  Both runs happen in child processes so the warm run gets
   no in-process caches — what is measured is exactly what a re-tune or CI
   job sees.  The best configuration is identical by construction, not luck:
   the warm engine replays the cold run's stored results, so the greedy
   driver takes the same decisions with zero backend calls.

2. **MCTS transposition DAG + warm ordering** — on the deterministic cost
   model, a cold ``run_mcts`` (transpositions on, fresh store) records its
   best time T and the experiment index where it first reached T; a warm
   re-run (same seed, store preloaded → expansion ordered by the measurement
   log) must reach T in **≤ half** the experiments on at least one kernel.
   Transposition on/off diagnostics (DAG edges, final bests) are recorded
   alongside.

The quick mode (``benchmarks/run.py --quick``) runs only gate 2 — the cheap
cost-model part — so it can serve as a CI smoke check.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

from .common import REPO, cli_env

WALL_BUDGET = 36
WALL_SCALE = 0.1
WALL_REPS = 2
MCTS_BUDGET = 600
MCTS_SEED = 0
MCTS_KERNELS = ("gemm", "covariance", "syr2k")

_CHILD_MARK = "WARMSTART_CHILD_RESULT:"


# ---------------------------------------------------------------------------
# Child process: one wallclock greedy tuning run against a store.
# ---------------------------------------------------------------------------


def _child(workload_name: str, store_path: str, budget: int,
           scale: float) -> None:
    from repro.core import PAPER_WORKLOADS, SearchSpace, WallclockBackend
    from repro.core.strategies import run_greedy

    w = PAPER_WORKLOADS[workload_name]
    backend = WallclockBackend(scale=scale, reps=WALL_REPS)
    t0 = time.perf_counter()
    log = run_greedy(w, SearchSpace(root=w.nest()), backend, budget=budget,
                     store=store_path)
    dt = time.perf_counter() - t0
    best = log.best()
    print(_CHILD_MARK + json.dumps({
        "experiments": len(log.experiments),
        "seconds": dt,
        "eps": len(log.experiments) / dt,
        "best_time_s": best.result.time_s,
        "best_pragmas": best.pragmas,
        "cache": log.cache,
    }))


def _run_child(workload_name: str, store_path: str) -> dict:
    # the store under test is passed explicitly
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_warm_start", "--child",
         workload_name, store_path, str(WALL_BUDGET), str(WALL_SCALE)],
        cwd=REPO, env=cli_env(), capture_output=True, text=True, timeout=900,
    )
    for line in proc.stdout.splitlines():
        if line.startswith(_CHILD_MARK):
            return json.loads(line[len(_CHILD_MARK):])
    raise RuntimeError(
        f"warm-start child for {workload_name} produced no result "
        f"(exit {proc.returncode}): {proc.stderr[-2000:]}")


# ---------------------------------------------------------------------------
# Gate 1: wallclock cold → warm, fresh process each.
# ---------------------------------------------------------------------------


def _tmp_store(prefix: str) -> str:
    fd, path = tempfile.mkstemp(prefix=prefix, suffix=".jsonl")
    os.close(fd)
    return path


def _drop_store(path: str) -> None:
    from repro.core import ResultStore

    ResultStore.drop_shared(path)   # release the process-wide fd
    try:
        os.unlink(path)
    except OSError:
        pass


def _wallclock_gate(emit) -> dict:
    out: dict = {}
    for wname in ("gemm", "covariance"):
        store = _tmp_store(f"warmstart_{wname}_")
        try:
            cold = _run_child(wname, store)
            warm = _run_child(wname, store)
        finally:
            _drop_store(store)
        speedup = warm["eps"] / cold["eps"]
        identical = warm["best_pragmas"] == cold["best_pragmas"]
        emit(f"  {wname:11s} cold={cold['eps']:8.1f} exp/s  "
             f"warm={warm['eps']:10.1f} exp/s ({speedup:7.1f}x)  "
             f"preloaded={warm['cache']['preloaded']}  "
             f"best_identical={identical}")
        out[wname] = {
            "cold_eps": cold["eps"], "warm_eps": warm["eps"],
            "warm_speedup": speedup,
            "cold_seconds": cold["seconds"], "warm_seconds": warm["seconds"],
            "preloaded": warm["cache"]["preloaded"],
            "best_identical": identical,
            "best_time_s": warm["best_time_s"],
            "pass": speedup >= 5.0 and identical,
        }
    return out


# ---------------------------------------------------------------------------
# Gate 2: MCTS transposition DAG + warm-ordered expansion (cost model).
# ---------------------------------------------------------------------------


def _mcts_gate(emit) -> dict:
    from repro.core import PAPER_WORKLOADS, CostModelBackend, SearchSpace
    from repro.core.strategies import run_mcts

    from .common import first_reaching

    be = CostModelBackend()
    out: dict = {}
    for wname in MCTS_KERNELS:
        w = PAPER_WORKLOADS[wname]
        store = _tmp_store(f"warmstart_mcts_{wname}_")
        try:
            cold = run_mcts(w, SearchSpace(root=w.nest()), be,
                            budget=MCTS_BUDGET, seed=MCTS_SEED, store=store)
            warm = run_mcts(w, SearchSpace(root=w.nest()), be,
                            budget=MCTS_BUDGET, seed=MCTS_SEED, store=store)
        finally:
            _drop_store(store)
        # store=False: the control must stay cold even under
        # ``benchmarks/run.py --store`` / CC_RESULT_STORE
        off = run_mcts(w, SearchSpace(root=w.nest()), be,
                       budget=MCTS_BUDGET, seed=MCTS_SEED,
                       transpositions=False, store=False)
        t_cold = cold.best().result.time_s
        i_cold = first_reaching(cold, t_cold)
        i_warm = first_reaching(warm, t_cold)
        halved = i_warm is not None and i_cold and i_warm <= i_cold / 2
        emit(f"  {wname:11s} cold_best={t_cold:8.4f}s @exp {i_cold:4d}  "
             f"warm reaches it @exp {i_warm}  "
             f"({'PASS' if halved else 'miss'})  "
             f"warm_links={warm.cache['transpositions']}  "
             f"warm_best={warm.best().result.time_s:.4f}s  "
             f"no_transpo_best={off.best().result.time_s:.4f}s")
        out[wname] = {
            "cold_best_s": t_cold,
            "cold_reached_at": i_cold,
            "warm_reached_at": i_warm,
            "warm_best_s": warm.best().result.time_s,
            "transposition_links_cold": cold.cache["transpositions"],
            "transposition_links_warm": warm.cache["transpositions"],
            "dag_nodes": cold.cache["dag_nodes"],
            "no_transpositions_best_s": off.best().result.time_s,
            "halved": bool(halved),
        }
    return out


# ---------------------------------------------------------------------------
# Benchmark proper
# ---------------------------------------------------------------------------


def main(emit=print, quick: bool = False):
    from .common import save_result

    rows: list[str] = []
    summary: dict = {}

    emit("\n=== warm start: MCTS transposition DAG + measurement-log "
         f"ordering (budget {MCTS_BUDGET}, seed {MCTS_SEED}) ===")
    mcts = _mcts_gate(emit)
    summary["mcts"] = mcts
    mcts_pass = any(v["halved"] for v in mcts.values())
    for wname, v in mcts.items():
        reached = v["warm_reached_at"]
        rows.append(
            f"warm_start_mcts_{wname},,cold@{v['cold_reached_at']};"
            f"warm@{reached};links={v['transposition_links_warm']}")

    wall_pass = True
    if not quick:
        emit(f"\n=== warm start: wallclock greedy cold vs warm, fresh "
             f"process each (budget {WALL_BUDGET}, scale {WALL_SCALE}) ===")
        wall = _wallclock_gate(emit)
        summary["wallclock"] = wall
        wall_pass = all(v["pass"] for v in wall.values())
        for wname, v in wall.items():
            rows.append(
                f"warm_start_wallclock_{wname},{1e6 / v['warm_eps']:.1f},"
                f"speedup={v['warm_speedup']:.1f};"
                f"best_identical={v['best_identical']}")

    summary["acceptance"] = {
        "mcts_halved_on_some_kernel": mcts_pass,
        "wallclock_5x_and_identical": wall_pass,
        "quick_mode": quick,
        "pass": mcts_pass and wall_pass,
    }
    emit(f"  acceptance: {'PASS' if summary['acceptance']['pass'] else 'FAIL'}"
         f" (mcts halved={mcts_pass}, wallclock 5x+identical={wall_pass}"
         f"{' [quick: wallclock skipped]' if quick else ''})")
    save_result("warm_start", summary)
    return rows


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        _, _, wname, store, budget, scale = sys.argv
        _child(wname, store, int(budget), float(scale))
    else:
        main()
