"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json``: ``bench/workloads/<cell>.json`` names its driver
(``bench/drivers/<driver>.py``) and holds its traffic mix, and
``bench/configs/<config>.json`` holds the model's sizes.  With ``--trace 0``
the last line of standard output carries the cell's end-to-end metrics;
with ``--trace 1`` a run of its own, partly under the profiler, carries the
per-layer metrics, each read by ``bench/metrics/<metric>.py``.  The numbers
that decide ``correct`` are printed last on standard error and last in the
result line, each beside its limit.

JAX's compilation cache lives in ``.jax_cache/bench/`` in the checkout.
The run exits non-zero, printing no result, when it finds no TPU or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    harness.enable_cache()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        harness.log(f"unknown workload {args.workload!r}")
        return 2
    devices = harness.check_device(cell["chips"])
    harness.log(f"[setup] devices found {time.perf_counter() - T_START:.2f} s "
                f"after the start")
    workload = harness.load_json("workloads", f"{cell['name']}.json")
    config = harness.load_json("configs", f"{cell['config']}.json")
    driver = importlib.import_module(f"bench.drivers.{workload['driver']}")
    ctx = harness.Context(cell, workload, config, args.seed, args.seconds,
                          bool(args.trace), T_START, devices)
    out = driver.run(ctx)
    line = result_line(spec, cell, config, workload, out, devices,
                       bool(args.trace))
    for name, c in out.checks.items():
        harness.log(f"[check] {name}: {c['value']!r} (limit {c['limit']!r})")
    harness.log(f"[check] correct: {out.correct}")
    print(json.dumps(line), flush=True)
    return 0


def result_line(spec: dict, cell: dict, config: dict, workload: dict, out,
                devices, traced: bool) -> dict:
    """The result's line: the cell's end-to-end metrics, or with a trace
    its per-layer metrics, the device, and last the compared numbers."""
    from bench import devtrace, harness, roofline

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed}
    if traced:
        readings = {"trace": out.trace, "record": out.record,
                    "config": config["config"], "workload": workload,
                    "architecture": config["architecture"],
                    "peak": roofline.peak(d.device_kind)}
        metrics = {}
        for m in spec["per_layer"]:
            if not _applies(m, cell, spec):
                continue
            value = harness.load_metric(m["name"]).read(readings)
            if value is None:
                harness.log(f"[metric] {m['name']}: nothing to read")
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"], device["window_s"] = devtrace.busy_share(out.trace)
        line["breakdown"] = devtrace.breakdown(out.trace)
    else:
        metrics = {m["name"]: {"value": out.metrics[m["name"]],
                               "unit": m["unit"]}
                   for m in spec["end_to_end"] if _applies(m, cell, spec)}
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = out.checks
    return line


def _applies(metric: dict, cell: dict, spec: dict) -> bool:
    """Whether ``cell`` reports ``metric``: the cells it lists, or, without
    a list, every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    moved = next((m for m in spec["end_to_end"]
                  if m["name"] == metric.get("moves")), None)
    return moved is None or _applies(moved, cell, spec)


if __name__ == "__main__":
    sys.exit(main())
