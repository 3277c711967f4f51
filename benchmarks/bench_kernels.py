"""Kernel-tuning gate: the repo's own Pallas kernels tuned end-to-end.

Closes the loop from ROADMAP item 2: the flash-attention and SSD kernels are
wrapped as :class:`~repro.core.kernelworkload.KernelWorkload` and tuned
through the unchanged :class:`~repro.core.session.TuningSession` path
(pallas backend: interpret-mode verification against the ``kernels/ref.py``
oracle + TPU-v5e cost-model objective).

The acceptance gate: the tuned attention schedule must beat the serving
default ``block_q = block_kv = 512`` on the cost-model objective, with both
schedules' interpret-mode outputs verified against the oracle at full
extents (identical results up to the summation-order tolerance — different
block sizes legitimately reorder the online-softmax accumulation).  The
winning schedules are written to ``results/kernel_schedules.json``, the file
``python -m repro.launch.serve --tuned-schedules`` installs into the
serving ``ModelConfig`` (tokens/sec is the end-to-end metric).

Registered in ``benchmarks.run --quick`` — a regression that makes tuning
lose to the untuned default (or miscompile a schedule) fails CI.
"""

from __future__ import annotations

import numpy as np

from repro.core import (Configuration, PallasBackend, SearchSpace, Tile,
                        TuningSession, attention_workload, ssd_workload)

from .common import results_dir, save_result

# Sequence length chosen so the untransformed root *is* the serving default
# schedule (block = full extent = 512): the baseline is guaranteed in-space
# and the comparison is tuned-vs-root on one tree.
SEQ = 512
BUDGET = 60
TILE_SIZES = (32, 64, 128, 256)
RTOL = ATOL = 2e-4          # PallasBackend verification tolerance


def _full_extent_check(w, nest, args, want):
    """Interpret-mode output of ``w`` under schedule ``nest`` at *full*
    extents vs the oracle; returns (ok, max_err)."""
    got = np.asarray(w.build(nest, interpret=True)(args))
    err = float(np.abs(got - np.asarray(want)).max())
    return bool(np.allclose(got, want, rtol=RTOL, atol=ATOL)), err, got


def main(emit=print):
    emit("\n=== kernel-tuning gate (KernelWorkload through TuningSession) "
         "===")
    backend = PallasBackend(scale=0.25, max_workers=4, interpret=True)
    session = TuningSession(backend, store=False)   # the gate measures cold
    schedules: dict = {}
    rows: list[str] = []

    # ---- flash attention: tuned vs the block_q=block_kv=512 default -------
    attn = attention_workload(batch=1, heads_q=8, heads_kv=2, seq_q=SEQ,
                              seq_kv=SEQ, head_dim=64, causal=True)
    root = Configuration()
    default_res = backend.evaluate(attn, root)      # root == 512/512 blocks
    space = SearchSpace(root=attn.nest(), tile_sizes=TILE_SIZES,
                        max_transformations=3)
    log = session.tune(attn, space, strategy="greedy", budget=BUDGET)
    best = log.best()
    tuned_nest = best.config.apply(attn.nest())
    tuned_params = attn.kernel_params(tuned_nest)
    tuned_time = best.result.time_s
    default_time = default_res.time_s

    args = attn.make_args()
    want = attn.reference(args)
    default_ok, default_err, default_out = _full_extent_check(
        attn, attn.nest(), args, want)
    tuned_ok, tuned_err, tuned_out = _full_extent_check(
        attn, tuned_nest, args, want)
    outputs_match = bool(np.allclose(tuned_out, default_out,
                                     rtol=RTOL, atol=ATOL))
    bitwise = bool(np.array_equal(tuned_out, default_out))

    default_params = attn.kernel_params(attn.nest())
    emit(f"  attention default {default_params}: "
         f"cost={default_time * 1e6:.2f}us verified={default_ok} "
         f"(max err {default_err:.2e})")
    emit(f"  attention tuned   {tuned_params}: "
         f"cost={tuned_time * 1e6:.2f}us verified={tuned_ok} "
         f"(max err {tuned_err:.2e}) via {best.pragmas or '<root>'}")
    emit(f"  tuned-vs-default outputs: allclose={outputs_match} "
         f"bitwise={bitwise} (bitwise is informational — block sizes "
         f"reorder the softmax accumulation)")
    attn_gate = bool(default_res.status == "ok" and default_ok and tuned_ok
                     and outputs_match and tuned_time <= default_time)
    schedules["attention"] = tuned_params
    speedup = default_time / tuned_time if tuned_time else float("inf")
    rows.append(f"kernels_attn_default,{default_time * 1e6:.3f},"
                f"cost-model blocks={default_params}")
    rows.append(f"kernels_attn_tuned,{tuned_time * 1e6:.3f},"
                f"cost-model blocks={tuned_params} "
                f"speedup={speedup:.1f}x verified={tuned_ok}")

    # ---- SSD scan: tuned chunk vs the serving default ssd_chunk=256 -------
    ssd = ssd_workload(heads=8, seq=SEQ, proj=64, state=64)
    base_cfg = Configuration().child(Tile(loops=("l",), sizes=(256,)))
    base_res = backend.evaluate(ssd, base_cfg)
    sspace = SearchSpace(root=ssd.nest(), tile_sizes=TILE_SIZES,
                         max_transformations=3)
    slog = session.tune(ssd, sspace, strategy="greedy", budget=BUDGET)
    sbest = slog.best()
    ssd_nest = sbest.config.apply(ssd.nest())
    ssd_params = ssd.kernel_params(ssd_nest)

    sargs = ssd.make_args()
    swant = ssd.reference(sargs)
    ssd_ok, ssd_err, _ = _full_extent_check(ssd, ssd_nest, sargs, swant)
    emit(f"  ssd default chunk=256: cost={base_res.time_s * 1e6:.2f}us "
         f"({base_res.status})")
    emit(f"  ssd tuned {ssd_params}: cost={sbest.result.time_s * 1e6:.2f}us "
         f"verified={ssd_ok} (max err {ssd_err:.2e})")
    schedules["ssd"] = ssd_params
    rows.append(f"kernels_ssd_default,{base_res.time_s * 1e6:.3f},"
                f"cost-model chunk=256")
    rows.append(f"kernels_ssd_tuned,{sbest.result.time_s * 1e6:.3f},"
                f"cost-model {ssd_params} verified={ssd_ok}")

    sched_path = results_dir() / "kernel_schedules.json"
    acceptance = {
        "pass": bool(attn_gate and ssd_ok),
        "attn_default_us": round(default_time * 1e6, 3),
        "attn_tuned_us": round(tuned_time * 1e6, 3),
        "attn_speedup": round(speedup, 2),
        "attn_verified": bool(default_ok and tuned_ok),
        "attn_outputs_match": outputs_match,
        "ssd_tuned_verified": ssd_ok,
        "experiments": len(log.experiments) + len(slog.experiments),
    }
    save_result("kernels", {
        "acceptance": acceptance,
        "schedules": schedules,
        "attn_pragmas": best.pragmas.splitlines(),
        "ssd_pragmas": sbest.pragmas.splitlines(),
    })
    import json
    with open(sched_path, "w", encoding="utf-8") as f:
        json.dump(schedules, f, indent=1)
    emit(f"  wrote {sched_path} (consumed by "
         f"`python -m repro.launch.serve --tuned-schedules`)")
    emit(f"  acceptance: {'PASS' if acceptance['pass'] else 'FAIL'}")
    return rows


if __name__ == "__main__":
    main()
