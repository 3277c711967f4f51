"""Autotune the Pallas gemm kernel's BlockSpec tiles with REAL execution.

Uses the wallclock backend (XLA:CPU at a reduced problem size — cache effects
are physically real on this machine) to rank tile configurations, verifies the
winning schedule's Pallas kernel against the jnp oracle in interpret mode, and
prints the pragma form + the block config you would pass to
``repro.kernels.ops.matmul`` on a TPU.

    PYTHONPATH=src python examples/autotune_gemm.py
    PYTHONPATH=src python examples/autotune_gemm.py --store sqlite:///tmp/tune.db

``--store`` attaches the persistent measurement store in its URI form —
``jsonl://path`` (the append-only log) or ``sqlite://path`` (indexed, for
long-lived stores); a bare path resolves by suffix.  Re-running with the
same store replays every previously measured structure with zero wallclock
spend.  The old spelling — constructing ``ResultStore(path)`` directly and
assuming JSONL — still works but emits a ``DeprecationWarning``; pass the
URI (or path) straight to ``TuningSession(store=...)`` instead.
"""

import argparse

import numpy as np

from repro.core import (GEMM, Configuration, PallasBackend, SearchSpace,
                        TuningSession, WallclockBackend)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--store", default=None, metavar="URI",
        help="persistent result store (jsonl://... / sqlite://... / path); "
             "re-runs warm-start from it instead of re-measuring")
    args = ap.parse_args()
    # tile/interchange only: wallclock on one CPU core can't measure
    # thread-parallelization (the cost model handles that; see quickstart)
    space = SearchSpace(
        root=GEMM.nest(),
        enable_parallelize=False,
        tile_sizes=(16, 32, 64, 128),
        max_transformations=2,
    )
    be = WallclockBackend(scale=0.12, reps=2)
    print("tuning gemm tiles on real XLA:CPU wallclock "
          f"(scale=0.12 → extents ≈ {GEMM.scaled(0.12).extents}) ...")
    # surrogate="analytic": under a tight wallclock budget, spend the
    # compile+run experiments on the cost model's top-ranked children first
    # (the old boolean alias for this is deprecated)
    # store=None (no flag) still defers to the CC_RESULT_STORE env default;
    # an explicit --store always wins over it
    session = TuningSession(be, surrogate="analytic", store=args.store)
    log = session.tune(GEMM, space, strategy="greedy", budget=60)
    if args.store and log.cache.get("preloaded"):
        print(f"(warm start: {log.cache['preloaded']} structures replayed "
              f"from {args.store})")
    best = log.best()
    print(f"\nbaseline (XLA default einsum): "
          f"{log.baseline.result.time_s*1e3:.1f} ms")
    print(f"best: {best.result.time_s*1e3:.1f} ms at experiment #{best.number}")
    print(best.pragmas or "(baseline wins — XLA's einsum is well tiled "
          "already; the pragmas matter on the TPU path)")

    # correctness gate: the same schedule as a Pallas kernel vs the oracle
    pb = PallasBackend(verify=True, interpret=True)
    res = pb.evaluate(GEMM, best.config)
    print(f"\npallas interpret-mode verification: {res.status} "
          f"(tpu-v5e cost-model projection {res.time_s:.4f}s)"
          if res.ok else f"pallas check: {res.status}: {res.note}")


if __name__ == "__main__":
    main()
