"""Async pipelined-session gate (PR 7): saturate the measurement fleet.

Two checks, both on the gemm cost-model search:

1. **Worker scaling** — run the identical seeded random-search job twice
   against a :class:`~repro.core.faults.FaultInjectingBackend` whose
   slow-injection stretches every measurement to a fixed wall time
   (deterministic results, sleep-dominated measurement — the profile the
   pipelined loop exists for): once serially, once through
   ``tune(async_workers=N)`` with an ``N``-worker supervised pool
   (pre-warmed so process spawn is excluded).  Gate on wall-clock speedup
   ``>= SCALING_FLOOR * N``, a byte-identical experiment log, and pool
   utilization having been surfaced in ``log.cache["pool"]`` (and *not*
   in the serial log).
2. **kill -9 / resume of an async run** — run the same spec as a
   checkpointing CLI subprocess with ``async_workers`` in the spec,
   SIGKILL it once the crash-safe sidecar exists, then rerun with
   ``--resume``.  Gate on the resumed run's experiment log (and best)
   being byte-identical to an uninterrupted async reference run —
   checkpoints are only written at quiescent points, so no in-flight
   measurement is ever lost or double-counted.

The gate row lands in ``results/async.json`` and (via ``run.py --json``)
in the cumulative ``BENCH_trajectory.json``.  Part of the ``--quick`` CI
smoke set; also exercised under plain pytest by ``tests/test_bench_smoke.py``.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

from repro.core import (CostModelBackend, FaultInjectingBackend, GEMM,
                        SearchSpace, TuningSession, TuningSpec)

from .common import cli_env, save_result

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKERS = 4
SCALING_FLOOR = 0.8           # required speedup: >= SCALING_FLOOR * WORKERS
BUDGET = 24
SLOW_S = 0.2                  # per-measurement injected wall time
SPACE_ARGS = {"tile_sizes": [16, 64, 256], "max_transformations": 3}
SEED = 7


def _space():
    return SearchSpace(root=GEMM.nest(),
                       tile_sizes=tuple(SPACE_ARGS["tile_sizes"]),
                       max_transformations=SPACE_ARGS["max_transformations"])


def _backend(workers: int) -> FaultInjectingBackend:
    # slow-only injection: deterministic results, sleep-dominated
    # measurement — each evaluation takes ~SLOW_S regardless of config
    return FaultInjectingBackend(inner=CostModelBackend(), slow=1.0,
                                 slow_s=SLOW_S, seed=SEED,
                                 process_workers=workers)


def _tune(backend, async_workers: int):
    sess = TuningSession(backend, store=False)
    t0 = time.perf_counter()
    log = sess.tune(GEMM, _space(), strategy="random", budget=BUDGET,
                    seed=3, async_workers=async_workers)
    return log, time.perf_counter() - t0


def _scaling(emit):
    serial_log, serial_s = _tune(_backend(0), async_workers=0)

    be = _backend(WORKERS)
    pool = be._ensure_pool()
    warmed = pool.warmup() if pool is not None else 0
    async_log, async_s = _tune(be, async_workers=WORKERS)
    be.close()

    speedup = serial_s / async_s if async_s > 0 else float("inf")
    floor = SCALING_FLOOR * WORKERS
    key = lambda log: [(e.number, e.config, e.result.time_s, e.parent)
                       for e in log.experiments]
    identical = key(serial_log) == key(async_log)
    best_match = (serial_log.best().result.time_s
                  == async_log.best().result.time_s
                  and serial_log.best().pragmas == async_log.best().pragmas)
    util = (async_log.cache or {}).get("pool")
    util_ok = (isinstance(util, dict) and util.get("tasks", 0) > 0
               and "pool" not in (serial_log.cache or {}))
    emit(f"  scaling: serial {serial_s:.2f}s vs async({WORKERS}w) "
         f"{async_s:.2f}s -> {speedup:.2f}x (floor {floor:.1f}x), "
         f"warmed={warmed}, identical={identical}, "
         f"pool busy_frac={util.get('busy_frac') if util else None}")
    ok = (speedup >= floor and identical and best_match and util_ok
          and warmed == WORKERS)
    return {
        "workers": WORKERS,
        "warmed": warmed,
        "budget": BUDGET,
        "slow_s": SLOW_S,
        "serial_seconds": round(serial_s, 3),
        "async_seconds": round(async_s, 3),
        "speedup": round(speedup, 3),
        "scaling_floor": floor,
        "identical_experiments": bool(identical),
        "best_match": bool(best_match),
        "pool_utilization": util,
        "utilization_surfaced": bool(util_ok),
    }, ok


def _kill9_resume_async(emit):
    # random search: the trajectory is completion-order independent, so
    # the resumed async run must reproduce the reference log byte for byte
    spec = TuningSpec(
        workload="gemm", strategy="random", strategy_args={"seed": 3},
        budget=150, backend="fault",
        backend_args={"inner": {"backend": "costmodel"},
                      "slow": 1.0, "slow_s": 0.015, "seed": SEED,
                      "process_workers": 2},
        space_args=dict(SPACE_ARGS), store=False,
        checkpoint_every=10, async_workers=2,
    )
    with tempfile.TemporaryDirectory() as tmp:
        spec_path = os.path.join(tmp, "spec.json")
        ref_path = os.path.join(tmp, "ref.json")
        res_path = os.path.join(tmp, "res.json")
        ck = os.path.join(tmp, "ck.pkl")
        spec.checkpoint = ck
        spec.save(spec_path)
        cmd = [sys.executable, "-m", "repro.core.session", spec_path,
               "--quiet"]

        ref = subprocess.run(cmd + ["--out", ref_path, "--checkpoint",
                                    os.path.join(tmp, "ref_ck.pkl")],
                             cwd=REPO, env=cli_env(), capture_output=True,
                             text=True, timeout=600)
        if ref.returncode != 0:
            emit(f"  kill9-async: reference run failed: {ref.stderr.strip()}")
            return {"reference_exit": ref.returncode}, False

        victim = subprocess.Popen(cmd + ["--out", os.path.join(tmp, "x.json")],
                                  cwd=REPO, env=cli_env(),
                                  stdout=subprocess.DEVNULL,
                                  stderr=subprocess.DEVNULL)
        deadline = time.time() + 120
        while (not os.path.exists(ck) and victim.poll() is None
               and time.time() < deadline):
            time.sleep(0.02)
        killed = victim.poll() is None
        if killed:
            victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=60)
        emit(f"  kill9-async: sidecar appeared, SIGKILL delivered={killed} "
             f"(rc={victim.returncode})")

        res = subprocess.run(cmd + ["--out", res_path, "--resume"],
                             cwd=REPO, env=cli_env(), capture_output=True,
                             text=True, timeout=600)
        ok = res.returncode == 0 and os.path.exists(res_path)
        identical = False
        if ok:
            with open(ref_path) as f:
                a = json.load(f)
            with open(res_path) as f:
                b = json.load(f)
            identical = a["experiments"] == b["experiments"]
        emit(f"  kill9-async: resume exit={res.returncode} "
             f"byte_identical_experiments={identical}")
        return {
            "reference_exit": ref.returncode,
            "sigkill_delivered": bool(killed),
            "resume_exit": res.returncode,
            "byte_identical_experiments": bool(identical),
        }, ok and killed and identical


def main(emit=print):
    t0 = time.time()
    sc, sc_pass = _scaling(emit)
    k9, k9_pass = _kill9_resume_async(emit)
    acceptance = {
        "pass": bool(sc_pass and k9_pass),
        "scaling": sc,
        "kill9_resume_async": k9,
    }
    save_result("async", {
        "workers": WORKERS,
        "budget": BUDGET,
        "acceptance": acceptance,
    })
    emit(f"  acceptance: {'PASS' if acceptance['pass'] else 'FAIL'}")
    return [
        f"async_pipelined_scaling,{(time.time() - t0) * 1e6 / BUDGET:.1f},"
        f"speedup={sc.get('speedup')}x@{WORKERS}w "
        f"resume_identical={k9.get('byte_identical_experiments')}",
    ]


if __name__ == "__main__":
    main()
