"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this container it runs reduced configs end-to-end (real training, real
checkpoints, real restarts); on a pod the same entry point launches the full
config onto the production mesh (``--mesh single|multi`` + jax.distributed
initialisation handled by the environment).
"""

from __future__ import annotations

import argparse


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="internlm2_1_8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", type=str, required=True,
                    help="checkpoint directory; a committed step found "
                         "there is resumed")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="use the reduced (CPU-feasible) config")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="full config (requires a pod)")
    ap.add_argument("--mesh", choices=["none", "single", "multi"],
                    default="none")
    args = ap.parse_args(argv)

    from repro import compile_cache
    compile_cache.enable()

    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig
    from repro.optim import OptimizerConfig
    from repro.train.train_loop import LoopConfig, train

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    mesh = rules = None
    if args.mesh != "none":
        from repro.launch.mesh import make_production_mesh
        from repro.models import sharding as sh
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        rules = dict(sh.DEFAULT_RULES)

    opt = OptimizerConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps,
                          factored_experts=cfg.n_experts >= 256)
    loop = LoopConfig(total_steps=args.steps, log_every=10,
                      ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.global_batch)
    res = train(cfg, opt, loop, data, mesh=mesh, rules=rules)
    print(f"[launch.train] {args.arch} finished at step {res.last_step}"
          + (f" (resumed from {res.restored_from})" if res.restored_from
             else ""))
    for s, l in res.losses:
        print(f"  step {s:5d}: loss {l:.4f}")


if __name__ == "__main__":
    main()
