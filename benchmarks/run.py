"""Benchmark harness — one module per paper table/figure.

  bench_pragma_stacking   paper Fig. 1 (pragma stacking on gemm)
  bench_autotune          paper Figs. 6–11 (greedy traces ± parallelize)
  bench_mcts_vs_greedy    paper §VIII / ProTuner (beyond-paper strategies)
  bench_eval_cache        evaluation-engine experiments/sec vs pre-PR path
  bench_warm_start        persistent-store warm starts + MCTS transposition DAG
  bench_surrogate         learned surrogate vs analytic ordering (wallclock)
  bench_session           TuningSpec → CLI end-to-end vs legacy driver (PR 4)
  bench_acquisition       EI vs LCB vs greedy shootout on one warm store (PR 5)
  bench_store             store migration + cross-workload surrogate transfer
  bench_faults            fault injection: retry/quarantine + kill-9 resume (PR 6)
  bench_async             async pipelined sessions: worker scaling + resume (PR 7)
  bench_fleet             fleet dispatcher: N-host scaling, kill-9 requeue,
                          warm serving from the federated cache (PR 10)
  bench_kernels           kernel-tuning gate: the repo's own Pallas kernels
                          (attention/SSD) tuned through TuningSession —
                          tuned must beat the block=512 serving default
  bench_roofline          §Roofline table from the 80-cell dry-run records

Prints a final ``name,us_per_call,derived`` CSV.  Run with
``PYTHONPATH=src python -m benchmarks.run``.  Flags:

* ``--only <name>`` — run one suite.
* ``--json BENCH_eval.json`` — write the rows as machine-readable JSON *and*
  append a gate row to the cumulative ``results/BENCH_trajectory.json`` (the
  perf trajectory consumed by later PRs — append, don't re-measure by hand).
* ``--store TARGET`` — set ``CC_RESULT_STORE`` for the run so every tuning
  engine warm-starts from (and feeds) the persistent result store at TARGET —
  a path or a ``jsonl://`` / ``sqlite://`` URI; ``--store-backend sqlite``
  forces the indexed backend for a plain path.
* ``--compact-store`` — maintenance mode: compact the ``--store`` store
  (newest record per key, drop corrupt/old-schema entries) and exit without
  running any suite.
* ``--migrate-store DST`` — maintenance mode: copy every record of the
  ``--store`` store into DST (path or URI — the JSONL ⇄ SQLite migration)
  and exit.
* ``--merge-stores SRC [SRC ...]`` — federation mode: merge the SRC stores
  into the ``--store`` store (newest record per key, conflict counters
  printed) and exit.
* ``--quick`` — smoke mode: only the cheap cost-model gate suites
  (``eval_cache`` + the cost-model half of ``warm_start`` + ``session`` +
  ``acquisition`` + ``faults`` + ``async`` + ``fleet`` + ``kernels``), and exit
  non-zero if any acceptance gate regressed.  This
  is the CI regression check; it is also runnable standalone:
  ``python -m benchmarks.run --quick --json out.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

def _trajectory_path() -> str:
    """The cumulative trajectory file, honoring the ``CC_BENCH_RESULTS``
    results-dir override (used by the pytest bench smoke test)."""
    from .common import results_dir

    return os.path.join(os.fspath(results_dir()), "BENCH_trajectory.json")


def _load_trajectory() -> list:
    try:
        with open(_trajectory_path()) as f:
            data = json.load(f)
        return data if isinstance(data, list) else []
    except (OSError, ValueError):
        return []       # missing or corrupt → start a fresh trajectory


def _collect_gates(ran: set[str]) -> dict:
    """Acceptance gates written by gate-defining suites — only for suites
    that ran *to completion* in this invocation (a stale on-disk gate from
    an earlier run must not be re-recorded under this run's label, so
    failed suites are excluded even though a gate file may exist)."""
    from .common import results_dir

    results = os.fspath(results_dir())
    gates: dict = {}
    for name in ("eval_cache", "warm_start", "surrogate", "session",
                 "acquisition", "store", "faults", "async", "fleet",
                 "kernels", "analysis"):
        if name not in ran:
            continue
        try:
            with open(os.path.join(results, f"{name}.json")) as f:
                acc = json.load(f).get("acceptance")
            if acc is not None:
                gates[name] = acc
        except (OSError, ValueError):
            pass
    return gates


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help="run one suite, or a comma-separated list of suites")
    ap.add_argument(
        "--json", type=str, default=None, metavar="BENCH_eval.json",
        help="write results as JSON: {suites: {name: {seconds, failed}}, "
             "rows: [{name, us_per_call, derived}]} and append the gate "
             "summary to results/BENCH_trajectory.json")
    ap.add_argument(
        "--store", type=str, default=None, metavar="TARGET",
        help="persistent result store: sets CC_RESULT_STORE so all tuning "
             "engines in this run start warm from TARGET (a path or a "
             "jsonl:// / sqlite:// URI) and append to it")
    ap.add_argument(
        "--store-backend", choices=("auto", "jsonl", "sqlite"),
        default="auto",
        help="force the --store backend for a plain path (auto resolves by "
             "URI scheme or path suffix; .sqlite/.sqlite3/.db → sqlite)")
    ap.add_argument(
        "--quick", action="store_true",
        help="cheap cost-model gate suites only; exit 1 on gate regression")
    ap.add_argument(
        "--compact-store", action="store_true",
        help="compact the --store store (newest record per key) and exit "
             "without running any suite")
    ap.add_argument(
        "--migrate-store", type=str, default=None, metavar="DST",
        help="copy every record of the --store store into DST (path or URI "
             "— the JSONL <-> SQLite migration) and exit")
    ap.add_argument(
        "--merge-stores", type=str, nargs="+", default=None, metavar="SRC",
        help="merge the SRC stores into the --store store (federation: "
             "newest record per key, conflict counters printed) and exit")
    args = ap.parse_args(argv)

    from repro import compile_cache
    compile_cache.enable()

    if args.json:
        d = os.path.dirname(args.json) or "."
        if not os.path.isdir(d):
            ap.error(f"--json: directory {d!r} does not exist")
    if args.store and args.store_backend != "auto" \
            and "://" not in args.store:
        args.store = f"{args.store_backend}://{args.store}"
    if args.compact_store:
        if not args.store:
            ap.error("--compact-store requires --store TARGET")
        from repro.core.resultstore import ResultStore

        store = ResultStore.shared(args.store)
        stats = store.compact()
        ResultStore.drop_shared(args.store)
        print(f"compacted {args.store}: kept {stats['kept']}, dropped "
              f"{stats['dropped_duplicates']} duplicate / "
              f"{stats['dropped_foreign']} old-schema / "
              f"{stats['dropped_corrupt']} corrupt record(s)")
        return
    if args.migrate_store:
        if not args.store:
            ap.error("--migrate-store requires --store TARGET")
        from repro.core.resultstore import migrate_store

        stats = migrate_store(args.store, args.migrate_store)
        print(f"migrated {stats['migrated']} record(s): "
              f"{stats['source']} -> {stats['dest']}")
        return
    if args.merge_stores:
        if not args.store:
            ap.error("--merge-stores requires --store TARGET")
        from repro.core.resultstore import ResultStore

        store = ResultStore.shared(args.store)
        stats = store.merge(*args.merge_stores)
        ResultStore.drop_shared(args.store)
        print(f"merged {stats['sources']} store(s) into {args.store}: "
              f"kept {stats['kept']}, added {stats['added']}, "
              f"{stats['conflicts']} conflict(s) "
              f"({stats['conflicts_by_scope'] or 'none'}), "
              f"{stats['duplicates']} duplicate(s)")
        return
    if args.store:
        os.environ["CC_RESULT_STORE"] = args.store

    from . import (bench_acquisition, bench_analysis, bench_async,
                   bench_autotune, bench_beyond_transforms, bench_eval_cache,
                   bench_faults, bench_fleet, bench_kernels,
                   bench_mcts_vs_greedy, bench_pragma_stacking,
                   bench_roofline, bench_session, bench_store,
                   bench_surrogate, bench_warm_start)

    suites = {
        "pragma_stacking": bench_pragma_stacking.main,
        "autotune": bench_autotune.main,
        "mcts_vs_greedy": bench_mcts_vs_greedy.main,
        "eval_cache": bench_eval_cache.main,
        "warm_start": bench_warm_start.main,
        "surrogate": bench_surrogate.main,
        "session": bench_session.main,
        "acquisition": bench_acquisition.main,
        "store": bench_store.main,
        "faults": bench_faults.main,
        "async": bench_async.main,
        "fleet": bench_fleet.main,
        "beyond_transforms": bench_beyond_transforms.main,
        "kernels": bench_kernels.main,
        "roofline": bench_roofline.main,
        "analysis": bench_analysis.main,
    }
    if args.quick:
        suites = {
            "eval_cache": bench_eval_cache.main,
            "warm_start": lambda: bench_warm_start.main(quick=True),
            "session": bench_session.main,
            "acquisition": bench_acquisition.main,
            "faults": bench_faults.main,
            "async": bench_async.main,
            "fleet": bench_fleet.main,
            "kernels": bench_kernels.main,
            "analysis": lambda: bench_analysis.main(quick=True),
        }
    if args.only:
        picked = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = [s for s in picked if s not in suites]
        if unknown or not picked:
            ap.error(f"--only: unknown suite(s) {unknown or [args.only]} "
                     f"(choose from {', '.join(suites)})")
        suites = {s: suites[s] for s in picked}

    all_rows: list[str] = []
    suite_meta: dict[str, dict] = {}
    for name, fn in suites.items():
        t0 = time.time()
        try:
            rows = fn()
            all_rows.extend(rows or [])
            suite_meta[name] = {"seconds": round(time.time() - t0, 2),
                                "failed": False}
            print(f"\n[{name}] done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:          # noqa: BLE001
            print(f"\n[{name}] FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            all_rows.append(f"{name},,FAILED:{type(e).__name__}")
            suite_meta[name] = {"seconds": round(time.time() - t0, 2),
                                "failed": True,
                                "error": f"{type(e).__name__}: {e}"}

    print("\n" + "=" * 60)
    print("name,us_per_call,derived")
    for r in all_rows:
        print(r)

    gates = _collect_gates(
        {n for n, m in suite_meta.items() if not m["failed"]})

    if args.json:
        structured = []
        for r in all_rows:
            parts = r.split(",", 2)
            name = parts[0]
            us = parts[1] if len(parts) > 1 else ""
            derived = parts[2] if len(parts) > 2 else ""
            structured.append({
                "name": name,
                "us_per_call": float(us) if us else None,
                "derived": derived,
            })
        payload = {"suites": suite_meta, "rows": structured, "gates": gates}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"\nwrote {args.json} ({len(structured)} rows)")

        # cumulative perf trajectory: later PRs append their gate rows here
        # instead of re-measuring earlier gates by hand
        traj = _load_trajectory()
        traj.append({
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "label": os.path.basename(args.json),
            "quick": args.quick,
            "suites": {n: m for n, m in suite_meta.items()},
            "gates": gates,
            # per-gate wall time: how long each gate-defining suite took
            # in this invocation (regression-hunting without re-running)
            "gate_seconds": {n: suite_meta[n]["seconds"] for n in gates
                             if n in suite_meta},
        })
        trajectory = _trajectory_path()
        os.makedirs(os.path.dirname(trajectory), exist_ok=True)
        # atomic replace: a crash mid-write must not destroy the cumulative
        # trajectory later PRs rely on
        tmp = trajectory + ".tmp"
        with open(tmp, "w") as f:
            json.dump(traj, f, indent=1)
        os.replace(tmp, trajectory)
        print(f"appended gate row #{len(traj)} to {trajectory}")

    failed_suites = [n for n, m in suite_meta.items() if m["failed"]]
    failed_gates = [n for n, a in gates.items() if not a.get("pass")]
    if failed_gates or failed_suites:
        print(f"\nGATE CHECK: failed suites={failed_suites} "
              f"failed gates={failed_gates}", file=sys.stderr, flush=True)
        if args.quick:
            sys.exit(1)
    elif args.quick:
        print("\nGATE CHECK: all gates pass")


if __name__ == "__main__":
    main()
