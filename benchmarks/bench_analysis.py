"""Static-analysis gate (PR 9): differentially-verified red-node prediction.

Two checks:

1. **Differential soundness matrix** — ≥2000 sampled schedules per workload
   (gemm/covariance/syr2k/attention/ssd), static verdicts cross-checked
   against the real backends (cost model; Pallas vmem/expressibility;
   wallclock's deterministic prefix via ``build_xla`` construction; plus a
   small full-verify Pallas subset).  Hard invariant: **zero false
   infeasibles** — anything a backend accepts must pass static analysis.
   Coverage of backend red nodes is reported per combo; on the deterministic
   paths the mirrors are exhaustive, so the syr2k gate requires ≥50% (it
   measures 100%).

2. **Pruning A/B on the syr2k space** — the same greedy tuning job with
   ``static_analysis`` off vs on, through a dispatch-counting backend.  Gate:
   byte-identical best (path, canonical time) and per-status experiment
   counts, strictly fewer backend dispatches, and ≥50% of the backend's
   red-node dispatches eliminated.

The gate row lands in ``results/analysis.json`` and (via ``run.py --json``)
in the cumulative ``BENCH_trajectory.json``.  Part of the ``--quick`` CI
smoke set; exercised under pytest by ``tests/test_bench_smoke.py``.
"""

from __future__ import annotations

import time

from repro.analysis import run_differential
from repro.core import (CostModelBackend, SearchSpace, TuningSession,
                        PAPER_WORKLOADS)
from repro.core.kernelworkload import kernel_workload
from repro.core.measure import PallasBackend, WallclockBackend

from .common import save_result

BUDGET = 250                 # A/B tuning budget on the syr2k space
SAMPLES = 2000               # per (workload, backend) differential combo
VERIFY_SAMPLES = 40          # full-verify Pallas subset (interpret runs)
SEED = 17


def _einsum(name):
    return PAPER_WORKLOADS[name]


# (workload-builder, backend-builder, dry?) — every workload appears in
# enough combos to clear the ≥2000-samples-per-workload acceptance bar on
# the cheap deterministic paths alone.
MATRIX = [
    ("gemm", "costmodel", False),
    ("covariance", "costmodel", False),
    ("syr2k", "costmodel", False),
    ("attention", "costmodel", False),
    ("ssd", "costmodel", False),
    ("gemm", "pallas-nf", False),
    ("covariance", "pallas-nf", False),
    ("syr2k", "pallas-nf", False),
    ("attention", "pallas-nf", False),
    ("ssd", "pallas-nf", False),
    ("gemm", "wallclock-dry", True),
    ("covariance", "wallclock-dry", True),
    ("syr2k", "wallclock-dry", True),
]

VERIFY_MATRIX = ["gemm", "attention", "ssd"]


def _workload(name):
    if name in ("attention", "ssd"):
        return kernel_workload(name)
    return _einsum(name)


def _backend(kind):
    if kind == "costmodel":
        return CostModelBackend()
    if kind == "pallas-nf":
        return PallasBackend(verify=False)
    if kind == "wallclock-dry":
        return WallclockBackend()
    raise AssertionError(kind)


def _differential(emit, samples, verify_samples):
    reports = []
    per_workload: dict[str, int] = {}
    for name, kind, dry in MATRIX:
        got = 0
        # small spaces (ssd) saturate the dedup'd sampler below the target:
        # take extra independently-seeded passes so the per-workload sample
        # totals still clear the acceptance bar
        for attempt in range(3):
            rep = run_differential(_workload(name), _backend(kind),
                                   samples=samples, seed=SEED + 101 * attempt,
                                   dry=dry, label=kind)
            reports.append(rep)
            got += rep.samples
            per_workload[name] = per_workload.get(name, 0) + rep.samples
            emit(f"  differential {name:>10s} × {kind:<13s} "
                 f"samples={rep.samples} backend_red={rep.backend_red} "
                 f"coverage={rep.coverage:.3f} sound={rep.sound}")
            if got >= samples:
                break
    for name in VERIFY_MATRIX:
        rep = run_differential(
            _workload(name), PallasBackend(scale=0.02, verify=True,
                                            interpret=True),
            samples=verify_samples, seed=SEED + 1, label="pallas-verify")
        reports.append(rep)
        per_workload[name] = per_workload.get(name, 0) + rep.samples
        emit(f"  differential {name:>10s} × pallas-verify "
             f"samples={rep.samples} backend_red={rep.backend_red} "
             f"coverage={rep.coverage:.3f} sound={rep.sound}")
    violations = sum(len(r.false_infeasible) for r in reports)
    syr2k = [r for r in reports if r.workload == "syr2k" and r.backend_red]
    syr2k_cov = (min(r.coverage for r in syr2k) if syr2k else 1.0)
    return reports, per_workload, violations, syr2k_cov


class _CountingBackend(CostModelBackend):
    """Counts what actually reaches the backend — static pruning must cut
    the red share of this, not just recolor results."""

    def __init__(self):
        super().__init__()
        self.dispatched = 0
        self.dispatched_red = 0

    def evaluate_many(self, workload, configs, nests=None):
        results = super().evaluate_many(workload, configs, nests=nests)
        self.dispatched += len(results)
        self.dispatched_red += sum(1 for r in results if not r.ok)
        return results


def _ab_pruning(emit):
    w = _einsum("syr2k")

    def run(static):
        be = _CountingBackend()
        session = TuningSession(be, store=False, static_analysis=static)
        log = session.tune(w, SearchSpace(root=w.nest()),
                           strategy="greedy", budget=BUDGET)
        return log, be

    log_a, be_a = run(False)
    log_b, be_b = run(True)
    best_a, best_b = log_a.best(), log_b.best()
    identical_best = (
        best_a.result.time_s == best_b.result.time_s
        and best_a.config.path_key() == best_b.config.path_key())
    identical_counts = (len(log_a.experiments) == len(log_b.experiments)
                        and log_a.counts() == log_b.counts())
    eliminated = (1.0 - be_b.dispatched_red / be_a.dispatched_red
                  if be_a.dispatched_red else 0.0)
    emit(f"  A/B syr2k greedy budget={BUDGET}: dispatched "
         f"{be_a.dispatched}->{be_b.dispatched} "
         f"(red {be_a.dispatched_red}->{be_b.dispatched_red}, "
         f"{eliminated:.0%} eliminated) identical_best={identical_best}")
    return {
        "budget": BUDGET,
        "dispatched_off": be_a.dispatched,
        "dispatched_on": be_b.dispatched,
        "dispatched_red_off": be_a.dispatched_red,
        "dispatched_red_on": be_b.dispatched_red,
        "red_dispatch_eliminated": round(eliminated, 4),
        "static_pruned": log_b.cache.get("static", {}).get("pruned", 0),
        "by_rule": log_b.cache.get("static", {}).get("by_rule", {}),
        "identical_best": bool(identical_best),
        "identical_counts": bool(identical_counts),
        "fewer_dispatches": be_b.dispatched < be_a.dispatched,
    }


def main(emit=print, quick: bool = False):
    t0 = time.time()
    samples = 600 if quick else SAMPLES
    verify_samples = 20 if quick else VERIFY_SAMPLES
    reports, per_workload, violations, syr2k_cov = _differential(
        emit, samples, verify_samples)
    ab = _ab_pruning(emit)
    acceptance = {
        "pass": bool(
            violations == 0
            and syr2k_cov >= 0.5
            and ab["identical_best"]
            and ab["identical_counts"]
            and ab["fewer_dispatches"]
            and ab["red_dispatch_eliminated"] >= 0.5),
        "soundness_violations": violations,
        "samples_per_workload": per_workload,
        "syr2k_min_coverage": round(syr2k_cov, 4),
        "ab": ab,
    }
    save_result("analysis", {
        "samples": samples,
        "verify_samples": verify_samples,
        "seed": SEED,
        "reports": [r.to_dict() for r in reports],
        "acceptance": acceptance,
    })
    emit(f"  acceptance: {'PASS' if acceptance['pass'] else 'FAIL'}")
    n = sum(r.samples for r in reports)
    return [
        f"analysis_differential,{(time.time() - t0) * 1e6 / max(n, 1):.1f},"
        f"violations={violations} syr2k_cov={syr2k_cov:.3f} "
        f"red_eliminated={ab['red_dispatch_eliminated']:.2f} "
        f"identical_best={ab['identical_best']}",
    ]


if __name__ == "__main__":
    main()
