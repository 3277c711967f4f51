"""Readings that a cell's correctness limit is set from, on the chip, in one
process (set-up is paid once):

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --rounds 2

For each seed: the benchmark's weights from that seed, ``--rounds`` rounds
of the cell's traffic through the program's timed path, a sample of the
finished requests as a run draws it, and two readings over the sample: the
program's served-token gaps below the float32 reference (widest and mean:
the lower readings), and the same for the float8 control, which judges at
each position the token that the reference computed in float8 puts first
(the upper readings).  One JSON line per seed.  The benchmark's own runs never
run the control.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    harness.enable_cache()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    devices = harness.check_device(cell["chips"])
    workload = harness.load_json("workloads", f"{cell['name']}.json")
    config = harness.load_json("configs", f"{cell['config']}.json")
    server = None
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(cell, workload, config, seed, 0.0, False,
                              t_start, devices)
        server, reading = calibrate_seed(ctx, server, args.rounds)
        print(json.dumps(reading), flush=True)
    return 0


def calibrate_seed(ctx, server, rounds: int):
    """(server, one seed's two readings); reuses ``server``'s compiled
    engine."""
    import gc

    from bench import harness
    from bench.drivers import serve_closed as sc
    from bench.traffic import ClosedLoop

    if server is None:
        server = sc.setup(ctx)
    else:
        server.engine.params = None
        gc.collect()
        server.ctx, server.key = ctx, harness.seed_key(ctx.seed)
        cfg = harness.program_config(ctx.config)
        server.engine.params = sc.make_params(server.ref, server.spec,
                                              server.key, cfg)
        server.traffic = ClosedLoop(ctx.workload["traffic"],
                                    server.spec["vocab_size"], ctx.seed)
    done = [sc.one_round(server) for _ in range(rounds)]
    params, server.engine.params = server.engine.params, None
    del params
    gc.collect()
    picked = sc.sample(done, ctx.seed, ctx.workload["check"]["tokens"])
    args = (server.ref, server.spec, server.key, picked)
    return server, {"workload": ctx.cell["name"], "seed": ctx.seed,
                    "program": sc.served_gaps(*args),
                    "control": sc.served_gaps(*args, "fp8")}


if __name__ == "__main__":
    sys.exit(main())
