"""Fault-tolerance gate (PR 6): injected faults must not change the answer.

Two checks, both against the same cost-model MCTS run:

1. **Fault-vs-clean equivalence** — re-run the tuning job through a
   :class:`~repro.core.faults.FaultInjectingBackend` injecting ~20%
   crashes + hangs (seeded), supervised by a
   :class:`~repro.core.faults.RetryPolicy`.  Gate on the faulty run
   reaching the **identical** best (pragmas and time) as the fault-free
   run, within 2× the experiments-to-best and a bounded wall clock — the
   retry/quarantine layer absorbs the faults without corrupting the search.
2. **kill -9 / resume** — run the same spec as a checkpointing CLI
   subprocess, SIGKILL it once the crash-safe sidecar exists, then rerun
   with ``--resume``.  Gate on the resumed run's experiment log (and best)
   being byte-identical to an uninterrupted reference run.

The gate row lands in ``results/faults.json`` and (via ``run.py --json``)
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
                        RetryPolicy, SearchSpace, TuningSession, TuningSpec)

from .common import cli_env, first_reaching, save_result

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BUDGET = 150
SPACE_ARGS = {"tile_sizes": [16, 64, 256], "max_transformations": 3}
SEED = 7
FAULT_ARGS = dict(crash=0.1, hang=0.1, seed=SEED, deadline_s=0.002)
RETRY = dict(max_attempts=4, backoff_s=0.001, jitter=0.0, quarantine_after=6)


def _space():
    return SearchSpace(root=GEMM.nest(),
                       tile_sizes=tuple(SPACE_ARGS["tile_sizes"]),
                       max_transformations=SPACE_ARGS["max_transformations"])


def _tune(backend, retry=None):
    sess = TuningSession(backend, store=False, retry=retry)
    t0 = time.time()
    log = sess.tune(GEMM, _space(), strategy="mcts", budget=BUDGET, seed=0)
    return log, time.time() - t0


def _fault_vs_clean(emit):
    clean, clean_s = _tune(CostModelBackend())
    faulty_be = FaultInjectingBackend(inner=CostModelBackend(), **FAULT_ARGS)
    faulty, faulty_s = _tune(faulty_be, retry=RetryPolicy(**RETRY))

    cb, fb = clean.best(), faulty.best()
    best_match = (fb.result.time_s == cb.result.time_s
                  and fb.pragmas == cb.pragmas)
    n_clean = first_reaching(clean, cb.result.time_s)
    n_faulty = first_reaching(faulty, cb.result.time_s)
    within_2x = n_faulty is not None and n_faulty <= 2 * max(1, n_clean or 1)
    injected = sum(v for k, v in faulty_be.faults.items()
                   if k.startswith("injected"))
    wall_bounded = faulty_s < max(60.0, 20.0 * clean_s + 10.0)
    emit(f"  fault-vs-clean: best_match={best_match} "
         f"(clean {cb.result.time_s:.6g} @#{n_clean}, "
         f"faulty @#{n_faulty}), {injected} faults injected, "
         f"faults={faulty.cache.get('faults')}, "
         f"wall {faulty_s:.1f}s vs clean {clean_s:.1f}s")
    return {
        "best_match": bool(best_match),
        "experiments_to_best_clean": n_clean,
        "experiments_to_best_faulty": n_faulty,
        "within_2x_experiments": bool(within_2x),
        "injected_faults": injected,
        "faults_counters": faulty.cache.get("faults"),
        "clean_seconds": round(clean_s, 2),
        "faulty_seconds": round(faulty_s, 2),
        "wall_bounded": bool(wall_bounded),
    }, best_match and within_2x and wall_bounded and injected > 0


def _kill9_resume(emit):
    # slow-only injection stretches the run (a kill window exists) without
    # perturbing any result, so the resumed trajectory must be byte-identical
    spec = TuningSpec(
        workload="gemm", strategy="mcts", strategy_args={"seed": 0},
        budget=BUDGET, backend="fault",
        backend_args={"inner": {"backend": "costmodel"},
                      "slow": 1.0, "slow_s": 0.015, "seed": SEED},
        space_args=dict(SPACE_ARGS), store=False,
        retry=dict(RETRY), checkpoint_every=10,
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
            emit(f"  kill9: reference run failed: {ref.stderr.strip()}")
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
        emit(f"  kill9: sidecar appeared, SIGKILL delivered={killed} "
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
        emit(f"  kill9: resume exit={res.returncode} "
             f"byte_identical_experiments={identical}")
        return {
            "reference_exit": ref.returncode,
            "sigkill_delivered": bool(killed),
            "resume_exit": res.returncode,
            "byte_identical_experiments": bool(identical),
        }, ok and killed and identical


def main(emit=print):
    t0 = time.time()
    fv, fv_pass = _fault_vs_clean(emit)
    k9, k9_pass = _kill9_resume(emit)
    acceptance = {
        "pass": bool(fv_pass and k9_pass),
        "fault_vs_clean": fv,
        "kill9_resume": k9,
    }
    save_result("faults", {
        "budget": BUDGET,
        "fault_args": {k: v for k, v in FAULT_ARGS.items()},
        "retry": RETRY,
        "acceptance": acceptance,
    })
    emit(f"  acceptance: {'PASS' if acceptance['pass'] else 'FAIL'}")
    return [
        f"faults_injected_recovery,{(time.time() - t0) * 1e6 / BUDGET:.1f},"
        f"best_match={fv.get('best_match')} "
        f"resume_identical={k9.get('byte_identical_experiments')}",
    ]


if __name__ == "__main__":
    main()
