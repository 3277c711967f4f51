"""Shared helpers for the benchmark harness."""

from __future__ import annotations

import json
import os
import pathlib
import time

RESULTS = pathlib.Path(__file__).parent / "results"
REPO = pathlib.Path(__file__).resolve().parent.parent


def cli_env() -> dict:
    """Environment for a benchmark's child processes: ``src`` importable,
    no ambient result store (the gates measure cold), and JAX held to the
    CPU.  The children measure cost models or XLA:CPU; a TPU belongs to one
    process, and the parent may already hold it."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.pop("CC_RESULT_STORE", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def results_dir() -> pathlib.Path:
    """Where benchmark artifacts land.  ``CC_BENCH_RESULTS`` overrides the
    in-repo ``benchmarks/results/`` — the bench smoke test points it at a
    tmpdir so a pytest run never mutates the repo's committed results."""
    override = os.environ.get("CC_BENCH_RESULTS")
    return pathlib.Path(override) if override else RESULTS


def save_result(name: str, payload: dict) -> pathlib.Path:
    d = results_dir()
    d.mkdir(parents=True, exist_ok=True)
    path = d / f"{name}.json"
    path.write_text(json.dumps(payload, indent=1, default=float))
    return path


def first_reaching(log, target: float, *, skip_baseline: bool = False
                   ) -> int | None:
    """Experiment number of the first ``ok`` result at or under ``target``
    seconds, or ``None`` — the experiments-to-best metric every warm-start/
    surrogate/acquisition gate reports.  ``skip_baseline`` excludes
    experiment 0 (gates comparing *transformed* children only: both runs
    share the identical untransformed baseline)."""
    for e in log.experiments:
        if skip_baseline and e.number == 0:
            continue
        if e.result.ok and e.result.time_s is not None \
                and e.result.time_s <= target:
            return e.number
    return None


def trace_csv(log) -> str:
    """experiment,time_s,status,best_so_far — the data behind Figs 6–11."""
    lines = ["experiment,time_s,status,best_so_far"]
    best = float("inf")
    for e in log.experiments:
        t = e.result.time_s if e.result.ok else ""
        if e.result.ok:
            best = min(best, e.result.time_s)
        lines.append(f"{e.number},{t},{e.result.status},"
                     f"{best if best < float('inf') else ''}")
    return "\n".join(lines)


def ascii_trace(log, width: int = 72, height: int = 14) -> str:
    """Terminal rendering of the autotuning progress figure."""
    import math

    pts = [(e.number, e.result.time_s) for e in log.experiments if e.result.ok]
    if not pts:
        return "(no successful experiments)"
    xs = [p[0] for p in pts]
    ys = [math.log10(max(p[1], 1e-9)) for p in pts]
    y0, y1 = min(ys), max(ys)
    if y1 - y0 < 1e-9:
        y1 = y0 + 1
    grid = [[" "] * width for _ in range(height)]
    best = float("inf")
    for (x, t), ly in zip(pts, ys):
        col = int((x / max(xs[-1], 1)) * (width - 1))
        row = int((1 - (ly - y0) / (y1 - y0)) * (height - 1))
        new_best = t < best
        best = min(best, t)
        grid[row][col] = "B" if new_best else "x"
    out = []
    for r, row in enumerate(grid):
        yv = 10 ** (y1 - (r / (height - 1)) * (y1 - y0))
        out.append(f"{yv:9.3f}s |" + "".join(row))
    out.append(" " * 11 + "+" + "-" * (width - 1))
    out.append(" " * 11 + f"experiments 0..{xs[-1]}   (B = new best, x = evaluated)")
    return "\n".join(out)
