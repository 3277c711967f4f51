"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Initialises (or restores) parameters, builds the engine, and runs a wave of
synthetic requests — the ``serve_step`` counterpart of launch.train.  It
records the engine's spans (``repro.spans``) over the wave and prints, for
each span name, the count and the total host time, and the host syncs per
decode step.  The wave's first call compiles, so these times are not a
serving speed: ``bench/`` measures that.

``--tuned-schedules`` closes the autotuning loop: it takes the
``kernel_schedules.json`` written by ``benchmarks/bench_kernels.py`` (the
winning block sizes of a :class:`~repro.core.kernelworkload.KernelWorkload`
tuning run) and installs them into the :class:`~repro.configs.base.
ModelConfig` serving knobs (``attn_q_chunk``, ``ssd_chunk``), so a tuned
kernel schedule is what the model path serves with.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging

_log = logging.getLogger("repro.launch.serve")


def apply_tuned_schedules(cfg, path):
    """Install tuned kernel schedules (``{"attention": {"block_q": ...},
    "ssd": {"chunk": ...}}``) into a :class:`ModelConfig`.

    The file is validated entry by entry: a kernel this build does not
    serve, a non-object params entry, or a block value that is not an
    integer (booleans included — JSON ``true`` is not a block size) is
    **warned about and skipped**, and every valid entry still applies.  A
    schedules file routinely outlives the build that wrote it — a tuning
    sweep may cover kernels a given serving config never installs — and
    rejecting the whole file over one stale row would silently throw away
    the tuned schedules that *do* apply.  The skips are loud (one warning
    per entry) so a comparison is never quietly mis-scoped.
    """
    from repro.core.kernelworkload import serve_overrides

    with open(path, encoding="utf-8") as f:
        schedules = json.load(f)
    if not isinstance(schedules, dict):
        raise ValueError(
            f"tuned schedules {path!r}: expected a JSON object of "
            f"{{kernel: params}}, got {type(schedules).__name__}")
    overrides = {}
    for kernel, params in schedules.items():
        if not isinstance(params, dict):
            _log.warning(
                "tuned schedules %s: %r params must be an object, got %s "
                "— skipping", path, kernel, type(params).__name__)
            continue
        bad = {k: v for k, v in params.items()
               if not isinstance(v, int) or isinstance(v, bool)}
        if bad:
            _log.warning(
                "tuned schedules %s: %r has non-integer block values %r "
                "— skipping", path, kernel, bad)
            continue
        try:
            overrides.update(serve_overrides(kernel, params))
        except (ValueError, KeyError) as e:
            _log.warning(
                "tuned schedules %s: unknown kernel %r (%s) — skipping",
                path, kernel, e)
    return dataclasses.replace(cfg, **overrides), overrides


def span_summary(kept) -> list[str]:
    """For each span name, its count and total host time, then the host
    syncs per decode step: the ``syncs`` of every ``serve.emit`` (one
    transfer of a step's tokens; the position stays on the host) over the
    ``serve.dispatch`` spans."""
    totals: dict[str, list] = {}
    for sp in kept:
        t = totals.setdefault(sp.name, [0, 0])
        t[0] += 1
        t[1] += sp.end_ns - sp.start_ns
    lines = [f"{name}: {n} spans, {ns / 1e6:.3f} ms"
             for name, (n, ns) in totals.items()]
    steps = totals.get("serve.dispatch", [0])[0]
    syncs = sum(sp.args.get("syncs", 0) for sp in kept
                if sp.name == "serve.emit")
    if steps:
        lines.append(f"host syncs per decode step: {syncs / steps:.2f} "
                     f"({syncs} over {steps} steps)")
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="internlm2_1_8b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="use the reduced (CPU-feasible) config")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="full config at its published widths")
    ap.add_argument("--ckpt", type=str, default=None,
                    help="checkpoint dir to restore params from")
    ap.add_argument("--tuned-schedules", type=str, default=None,
                    metavar="JSON",
                    help="kernel_schedules.json from bench_kernels — "
                         "installs the tuned block sizes into the model "
                         "config (attn_q_chunk / ssd_chunk)")
    args = ap.parse_args(argv)

    from repro import compile_cache
    compile_cache.enable()

    import jax
    import numpy as np

    from repro import spans
    from repro.configs.base import get_config
    from repro.models.model import build_model
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.tuned_schedules:
        cfg, overrides = apply_tuned_schedules(cfg, args.tuned_schedules)
        print(f"[launch.serve] tuned schedules from "
              f"{args.tuned_schedules}: {overrides}")
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    if args.ckpt:
        from repro.train import checkpoint as ckpt
        step = ckpt.latest_step(args.ckpt)
        if step is not None:
            from repro.optim import OptimizerConfig, init_opt_state
            opt = init_opt_state(OptimizerConfig(), params)
            params, _ = ckpt.restore(args.ckpt, step, (params, opt))
            print(f"[launch.serve] restored params from step {step}")

    eng = ServeEngine(cfg, params, max_batch=args.requests,
                      max_seq=args.max_seq)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=list(rng.integers(1, cfg.vocab_size,
                                             args.prompt_len)),
                    max_new_tokens=args.max_new)
            for _ in range(args.requests)]
    spans.clear()
    with spans.recording():
        out = eng.generate(reqs)
    tok = sum(len(r.out) for r in out)
    print(f"[launch.serve] {args.arch}: {tok} tokens / {len(reqs)} requests")
    for line in span_summary(spans.recorded()):
        print(f"[launch.serve] {line}")
    for i, r in enumerate(out[:4]):
        print(f"  req{i}: {r.out[:10]}{'…' if len(r.out) > 10 else ''}")


if __name__ == "__main__":
    main()
