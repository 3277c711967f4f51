"""Fleet tuning-as-a-service gate (PR 10): saturate N hosts and serve
warm requests from the federated cache.

Three checks, all against a real :class:`~repro.fleet.server.Dispatcher`
(+ ``FleetHTTPServer`` on an ephemeral port) with real
``python -m repro.fleet.worker`` subprocesses — the same processes a
multi-host deployment would run, just colocated:

1. **Fleet scaling** — submit ``WORKERS`` independent seeded jobs against
   a slow-injection :class:`~repro.core.faults.FaultInjectingBackend`
   (deterministic results, sleep-dominated measurement — the profile the
   fleet exists for) to a dispatcher with ``WORKERS`` registered worker
   processes, and run the identical specs serially in-process as the
   reference.  Gate on wall-clock speedup ``>= SCALING_FLOOR * WORKERS``
   and every fleet job's experiment log being byte-identical to its
   serial twin (same spec → same trajectory, wherever it ran).
2. **kill -9 a worker mid-job** — submit one checkpointing job, SIGKILL
   the worker process it was assigned to once the crash-safe sidecar
   exists, and let the dispatcher's heartbeat monitor requeue it with
   ``resume=True`` onto a surviving worker.  Gate on the finished job's
   experiment log being byte-identical to an uninterrupted reference run
   — the blind requeue loses nothing and double-counts nothing.
3. **Warm cache serving** — submit a spec that leaves ``store`` unset
   (federation policy: worker-local store, warm-primed from
   ``GET /store``, uploaded back on completion), then submit the
   *identical* spec again.  The cold run must have dispatched real
   measurements (``injected_slow`` > 0 with a ``slow=1.0`` fault
   backend every true backend dispatch is counted); the re-submitted job
   must be served entirely from the federated cache — **zero** backend
   dispatches (no ``injected_slow`` counts at all) and the identical
   best.

The gate row lands in ``results/fleet.json`` and (via ``run.py --json``)
in the cumulative ``BENCH_trajectory.json``.  Part of the ``--quick`` CI
smoke set; also exercised under plain pytest by
``tests/test_bench_smoke.py``.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

from repro.core.session import TuningSpec
from repro.fleet import Dispatcher, FleetHTTPServer
from repro.fleet.protocol import http_json

from .common import cli_env, save_result

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKERS = 4
SCALING_FLOOR = 0.8           # required speedup: >= SCALING_FLOOR * WORKERS
BUDGET = 24
SLOW_S = 0.2                  # per-measurement injected wall time
SPACE_ARGS = {"tile_sizes": [16, 64, 256], "max_transformations": 3}
SEED = 7                      # fault-injection seed (slow=1.0 → don't care)
SCALING_SEEDS = (3, 4, 5, 6)  # one independent search per fleet worker
HEARTBEAT_TIMEOUT_S = 1.5     # short deadline so the kill-9 requeue is quick


def _spec_doc(seed: int, *, budget: int = BUDGET, slow_s: float = SLOW_S,
              store=False, **extra) -> dict:
    """A TuningSpec document for the slow-injection cost-model search.
    ``store=None`` omits the field — the fleet's "defer to federation"
    policy — while ``False`` pins the job cold."""
    doc = {
        "workload": "gemm", "strategy": "random",
        "strategy_args": {"seed": seed}, "budget": budget,
        "backend": "fault",
        "backend_args": {"inner": {"backend": "costmodel"},
                         "slow": 1.0, "slow_s": slow_s, "seed": SEED},
        "space_args": dict(SPACE_ARGS),
        "store": store,
    }
    doc.update(extra)
    if doc["store"] is None:
        del doc["store"]
    return doc


class _Fleet:
    """Dispatcher + HTTP server in-process, worker subprocesses out."""

    def __init__(self, tmp: str, n_workers: int):
        self.dispatcher = Dispatcher(
            spool_dir=os.path.join(tmp, "spool"),
            lint=True, lint_samples=25,
            heartbeat_timeout_s=HEARTBEAT_TIMEOUT_S,
            federation_interval_s=0.5)
        self.server = FleetHTTPServer(self.dispatcher, ("127.0.0.1", 0))
        self.port = self.server.port
        threading.Thread(target=self.server.serve_forever,
                         name="bench-fleet-server", daemon=True).start()
        self.workers: dict[str, subprocess.Popen] = {}
        for i in range(n_workers):
            name = f"bench-w{i + 1}"
            self.workers[name] = subprocess.Popen(
                [sys.executable, "-m", "repro.fleet.worker",
                 "--connect", f"127.0.0.1:{self.port}",
                 "--name", name,
                 "--workdir", os.path.join(tmp, name),
                 "--poll-interval", "0.05",
                 "--heartbeat-interval", "0.25"],
                cwd=REPO, env=cli_env(),
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    def status(self) -> dict:
        return http_json("127.0.0.1", self.port, "GET", "/status")

    def submit(self, doc: dict) -> dict:
        return http_json("127.0.0.1", self.port, "POST", "/submit",
                         {"spec": doc})

    def wait_registered(self, n: int, timeout_s: float = 120.0) -> None:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            alive = [w for w in self.status()["workers"].values()
                     if not w["dead"]]
            if len(alive) >= n:
                return
            time.sleep(0.05)
        raise TimeoutError(f"{n} fleet workers never registered")

    def wait_done(self, job_ids, timeout_s: float = 300.0) -> dict:
        deadline = time.time() + timeout_s
        states: dict = {}
        while time.time() < deadline:
            jobs = self.status()["jobs"]
            states = {j: jobs[j]["state"] for j in job_ids}
            if all(s in ("done", "failed") for s in states.values()):
                return states
            time.sleep(0.05)
        raise TimeoutError(f"fleet jobs never finished: {states}")

    def job_log(self, job_id: str) -> "dict | None":
        # the bench runs the dispatcher in-process, so it can read the full
        # worker-reported log (job.public() only carries the summary)
        return self.dispatcher._jobs[job_id].log

    def kill_worker(self, name: str) -> None:
        proc = self.workers[name]
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)

    def close(self) -> None:
        for proc in self.workers.values():
            if proc.poll() is None:
                proc.terminate()
        for proc in self.workers.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        self.server.shutdown()
        self.server.server_close()      # also closes the dispatcher


def _serial_reference(emit):
    """The same SCALING_SEEDS specs, run back-to-back in this process —
    the one-host baseline the fleet has to beat."""
    logs: dict[int, dict] = {}
    t0 = time.perf_counter()
    for seed in SCALING_SEEDS:
        logs[seed] = TuningSpec.from_dict(_spec_doc(seed)).run().to_dict()
    serial_s = time.perf_counter() - t0
    emit(f"  serial reference: {len(SCALING_SEEDS)} jobs in {serial_s:.2f}s")
    return logs, serial_s


def _scaling(fleet: _Fleet, serial_logs: dict, serial_s: float, emit):
    t0 = time.perf_counter()
    jobs = {seed: fleet.submit(_spec_doc(seed))["job_id"]
            for seed in SCALING_SEEDS}
    states = fleet.wait_done(jobs.values())
    fleet_s = time.perf_counter() - t0

    speedup = serial_s / fleet_s if fleet_s > 0 else float("inf")
    floor = SCALING_FLOOR * WORKERS
    all_done = all(s == "done" for s in states.values())
    identical = all_done and all(
        fleet.job_log(jid)["experiments"]
        == serial_logs[seed]["experiments"]
        for seed, jid in jobs.items())
    st = fleet.status()
    distinct = len({st["jobs"][jid]["worker"] for jid in jobs.values()})
    emit(f"  scaling: serial {serial_s:.2f}s vs fleet({WORKERS}w) "
         f"{fleet_s:.2f}s -> {speedup:.2f}x (floor {floor:.1f}x), "
         f"identical={identical}, distinct_workers={distinct}")
    ok = speedup >= floor and all_done and identical
    return {
        "workers": WORKERS,
        "jobs": len(jobs),
        "budget": BUDGET,
        "slow_s": SLOW_S,
        "serial_seconds": round(serial_s, 3),
        "fleet_seconds": round(fleet_s, 3),
        "speedup": round(speedup, 3),
        "scaling_floor": floor,
        "all_done": bool(all_done),
        "identical_experiments": bool(identical),
        "distinct_workers": distinct,
    }, ok


def _kill9_requeue_resume(fleet: _Fleet, tmp: str, emit):
    # random search: the trajectory is completion-order independent, so the
    # requeued job — resumed blind from the spool checkpoint sidecar by a
    # *different* worker process — must reproduce the uninterrupted
    # reference log byte for byte
    doc = _spec_doc(31, budget=150, slow_s=0.02, checkpoint_every=10)
    ref_doc = dict(doc, checkpoint=os.path.join(tmp, "ref.ck.pkl"))
    ref = TuningSpec.from_dict(ref_doc).run().to_dict()

    jid = fleet.submit(doc)["job_id"]
    deadline = time.time() + 60
    victim_wid = None
    while time.time() < deadline:
        job = fleet.status()["jobs"][jid]
        if job["state"] == "running" and job["worker"]:
            victim_wid = job["worker"]
            break
        time.sleep(0.01)
    if victim_wid is None:
        emit("  kill9: job was never assigned")
        return {"assigned": False}, False
    victim_name = fleet.status()["workers"][victim_wid]["name"]
    ck = fleet.dispatcher._jobs[jid].spec["checkpoint"]
    while not os.path.exists(ck) and time.time() < deadline:
        time.sleep(0.01)
    sidecar = os.path.exists(ck)
    fleet.kill_worker(victim_name)
    emit(f"  kill9: sidecar appeared={sidecar}, SIGKILL -> {victim_name}")

    state = fleet.wait_done([jid], timeout_s=180.0)[jid]
    job = fleet.status()["jobs"][jid]
    log = fleet.job_log(jid)
    identical = (state == "done" and log is not None
                 and log["experiments"] == ref["experiments"])
    emit(f"  kill9: state={state} requeues={job['requeues']} "
         f"resumed_on={job['worker']} "
         f"byte_identical_experiments={identical}")
    ok = sidecar and state == "done" and job["requeues"] >= 1 and identical
    return {
        "sidecar_before_kill": bool(sidecar),
        "killed_worker": victim_name,
        "state": state,
        "requeues": job["requeues"],
        "byte_identical_experiments": bool(identical),
    }, ok


def _warm_cache(fleet: _Fleet, emit):
    # store left unset → federation policy: the worker primes a local store
    # from GET /store and uploads it back, so the re-submitted spec replays
    # from cache.  slow=1.0 counts *every* true backend dispatch in
    # ``injected_slow`` — absent/zero on the warm job is the zero-dispatch
    # proof (cache "misses" also count never-dispatched red nodes, so the
    # miss counter alone cannot distinguish warm from cold).
    doc = _spec_doc(11, budget=20, slow_s=0.05, store=None)

    def run(label):
        t0 = time.perf_counter()
        jid = fleet.submit(dict(doc))["job_id"]
        state = fleet.wait_done([jid])[jid]
        wall = time.perf_counter() - t0
        res = fleet.status()["jobs"][jid]["result"] or {}
        cache = res.get("cache") or {}
        dispatches = (cache.get("faults") or {}).get("injected_slow", 0)
        best = (res.get("best") or {}).get("time_s")
        emit(f"  warm-cache: {label} job {jid} {state} in {wall:.2f}s — "
             f"backend dispatches={dispatches}, preloaded="
             f"{cache.get('preloaded', 0)}, best={best}")
        return {"state": state, "wall_s": round(wall, 3),
                "backend_dispatches": dispatches,
                "preloaded": cache.get("preloaded", 0),
                "hits": cache.get("hits", 0), "best_s": best}

    cold = run("cold")
    warm = run("re-submitted")
    ok = (cold["state"] == "done" and warm["state"] == "done"
          and cold["backend_dispatches"] > 0
          and warm["backend_dispatches"] == 0
          and warm["preloaded"] > 0
          and warm["best_s"] == cold["best_s"])
    emit(f"  warm-cache: zero_dispatch={warm['backend_dispatches'] == 0} "
         f"identical_best={warm['best_s'] == cold['best_s']} "
         f"({'PASS' if ok else 'miss'})")
    return {"cold": cold, "warm": warm,
            "zero_backend_dispatches": warm["backend_dispatches"] == 0,
            "identical_best": warm["best_s"] == cold["best_s"]}, ok


def main(emit=print):
    t0 = time.time()
    emit(f"\n=== fleet dispatcher: {WORKERS}-worker scaling, kill -9 "
         f"requeue/resume, federated warm cache ===")
    # warm the door-lint path once so one-time import cost stays out of the
    # timed fleet window (the serial reference never lints)
    from repro.analysis.lint import lint_spec
    lint_spec(TuningSpec.from_dict(_spec_doc(SCALING_SEEDS[0])), samples=8)

    with tempfile.TemporaryDirectory(prefix="bench_fleet_") as tmp:
        fleet = _Fleet(tmp, WORKERS)
        try:
            # the workers boot (python + session imports) while the serial
            # reference runs, so spawn cost is excluded from both arms
            serial_logs, serial_s = _serial_reference(emit)
            fleet.wait_registered(WORKERS)
            sc, sc_pass = _scaling(fleet, serial_logs, serial_s, emit)
            k9, k9_pass = _kill9_requeue_resume(fleet, tmp, emit)
            wm, wm_pass = _warm_cache(fleet, emit)
        finally:
            fleet.close()

    acceptance = {
        "pass": bool(sc_pass and k9_pass and wm_pass),
        "scaling": sc,
        "kill9_requeue_resume": k9,
        "warm_cache": wm,
    }
    save_result("fleet", {
        "workers": WORKERS,
        "budget": BUDGET,
        "acceptance": acceptance,
    })
    emit(f"  acceptance: {'PASS' if acceptance['pass'] else 'FAIL'} "
         f"(scaling={sc_pass}, kill9={k9_pass}, warm={wm_pass})")
    return [
        f"fleet_scaling,{(time.time() - t0) * 1e6 / BUDGET:.1f},"
        f"speedup={sc.get('speedup')}x@{WORKERS}w "
        f"distinct_workers={sc.get('distinct_workers')}",
        f"fleet_kill9,,requeued_resume_identical="
        f"{k9.get('byte_identical_experiments')}",
        f"fleet_warm,,dispatches cold={wm['cold']['backend_dispatches']} "
        f"warm={wm['warm']['backend_dispatches']} "
        f"identical_best={wm.get('identical_best')}",
    ]


if __name__ == "__main__":
    main()
