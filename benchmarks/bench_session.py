"""Session smoke gate (PR 4): a declarative TuningSpec run end to end.

The ask/tell redesign's CI contract is that a whole tuning job round-trips
through one JSON document and one CLI entry point:

1. write a :class:`~repro.core.session.TuningSpec` to a tmpdir,
2. execute it via ``python -m repro.core.session spec.json --out log.json``
   in a fresh subprocess (cold — no ambient result store),
3. gate on: zero exit, a well-formed ``TuningLog`` JSON, and the CLI run's
   best configuration being **identical** (pragmas and time) to the legacy
   ``run_greedy`` driver's on the same workload/space/budget — the
   session-vs-shim equivalence, checked across a process boundary.

The gate row lands in ``results/session.json`` and (via ``run.py --json``)
in the cumulative ``BENCH_trajectory.json``.  Part of the ``--quick`` CI
smoke set; also exercised under plain pytest by ``tests/test_bench_smoke.py``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

from repro.core import GEMM, CostModelBackend, SearchSpace, TuningSpec
from repro.core.strategies import run_greedy

from .common import cli_env, save_result

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BUDGET = 120
SPACE_ARGS = {"tile_sizes": [16, 64, 256], "max_transformations": 3}


def main(emit=print):
    spec = TuningSpec(workload="gemm", strategy="greedy", budget=BUDGET,
                      backend="costmodel", space_args=dict(SPACE_ARGS))

    with tempfile.TemporaryDirectory() as tmp:
        spec_path = os.path.join(tmp, "spec.json")
        log_path = os.path.join(tmp, "log.json")
        spec.save(spec_path)

        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-m", "repro.core.session", spec_path,
             "--out", log_path],
            cwd=REPO, env=cli_env(), capture_output=True, text=True,
            timeout=600,
        )
        cli_seconds = time.time() - t0
        emit(f"  CLI: exit={proc.returncode} in {cli_seconds:.1f}s "
             f"({proc.stdout.strip() or proc.stderr.strip()})")
        cli_log = None
        if proc.returncode == 0 and os.path.exists(log_path):
            with open(log_path) as f:
                cli_log = json.load(f)

    # the reference: the legacy shim, in-process, cold
    space = SearchSpace(root=GEMM.nest(),
                        tile_sizes=tuple(SPACE_ARGS["tile_sizes"]),
                        max_transformations=SPACE_ARGS["max_transformations"])
    legacy = run_greedy(GEMM, space, CostModelBackend(), budget=BUDGET,
                        store=False)
    legacy_best = legacy.best()

    def best_of(payload):
        ok = [e for e in payload["experiments"] if e["status"] == "ok"]
        return min(ok, key=lambda e: e["time_s"]) if ok else None

    cli_best = best_of(cli_log) if cli_log else None
    match = (cli_best is not None
             and cli_best["time_s"] == legacy_best.result.time_s
             and cli_best["pragmas"] == legacy_best.pragmas.splitlines()
             and len(cli_log["experiments"]) == len(legacy.experiments))
    emit(f"  best: cli={cli_best['time_s'] if cli_best else None} "
         f"legacy={legacy_best.result.time_s} match={match}")

    acceptance = {
        "pass": bool(proc.returncode == 0 and match),
        "cli_exit": proc.returncode,
        "cli_seconds": round(cli_seconds, 2),
        "best_match_vs_legacy": bool(match),
        "experiments": len(legacy.experiments),
    }
    save_result("session", {
        "spec": spec.to_dict(),
        "acceptance": acceptance,
        "legacy_best_time_s": legacy_best.result.time_s,
    })
    emit(f"  acceptance: {'PASS' if acceptance['pass'] else 'FAIL'}")
    return [
        f"session_cli_spec,{cli_seconds * 1e6 / max(1, BUDGET):.1f},"
        f"exit={proc.returncode} best_match={match}",
    ]


if __name__ == "__main__":
    main()
