"""End-to-end driver: train an LM with checkpointing, a simulated mid-run
node failure, an automatic restart, and a straggler watchdog — the full
production loop at laptop scale.

Default config (~12M params, 60 steps) finishes in a few minutes on this
1-core container; ``--hundred-m --steps 300`` is the full ~100M/300-step run
for real hardware.

    PYTHONPATH=src python examples/train_lm.py [--steps 60] [--hundred-m]
"""

import argparse
import dataclasses
import pathlib
import shutil

from repro.configs.base import get_config
from repro.data.pipeline import DataConfig
from repro.models.model import count_params_from_specs
from repro.optim import OptimizerConfig
from repro.train.fault_tolerance import FailureInjector, run_with_restarts
from repro.train.train_loop import LoopConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--ckpt-dir", type=str, default=str(
        pathlib.Path(__file__).resolve().parents[1] / "runs" / "train_lm"))
    ap.add_argument("--fail-at", type=int, default=30,
                    help="inject a simulated node failure at this step")
    ap.add_argument("--hundred-m", action="store_true",
                    help="full ~100M-param config (for real hardware)")
    args = ap.parse_args()

    if args.hundred_m:
        # ~100M params: internlm2 family at width 768 / 12 layers
        cfg = dataclasses.replace(
            get_config("internlm2_1_8b"),
            n_layers=12, d_model=768, n_heads=12, n_kv_heads=4, head_dim=64,
            d_ff=2048, vocab_size=32000, dtype="float32",
            param_dtype="float32", scan_layers=True, remat="none")
    else:
        cfg = dataclasses.replace(
            get_config("internlm2_1_8b"),
            n_layers=6, d_model=256, n_heads=8, n_kv_heads=4, head_dim=32,
            d_ff=768, vocab_size=32000, dtype="float32",
            param_dtype="float32", scan_layers=True, remat="none")
    print(f"model: {cfg.name} variant, params="
          f"{count_params_from_specs(cfg)/1e6:.1f}M")

    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    opt = OptimizerConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps)
    loop = LoopConfig(total_steps=args.steps, log_every=10,
                      ckpt_every=max(10, args.steps // 4),
                      ckpt_dir=args.ckpt_dir)
    seq, gb = (256, 8) if args.hundred_m else (128, 4)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq, global_batch=gb)

    injector = FailureInjector(fail_at_steps=(args.fail_at,))

    def attempt(_start):
        return train(cfg, opt, loop, data, injector=injector)

    res, restarts = run_with_restarts(
        attempt, max_restarts=2,
        on_restart=lambda n, e: print(f"  !! {e} — restarting ({n})"))

    print(f"\nfinished at step {res.last_step} with {restarts} restart(s); "
          f"restored from step {res.restored_from}")
    print("loss curve:")
    for s, l in res.losses:
        print(f"  step {s:4d}: {l:.4f}")
    if res.straggler_flags:
        print("straggler-flagged steps:", res.straggler_flags)
    first, last = res.losses[0][1], res.losses[-1][1]
    print(f"\nloss {first:.3f} → {last:.3f} "
          f"({'learning ✓' if last < first - 0.3 else 'check config'})")


if __name__ == "__main__":
    main()
