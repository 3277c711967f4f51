"""Drive the system's main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: device, kernels, tune, serve
    python chip_smoke.py --chips 4    # four chips: sharded training only

One chip, in order (any failure exits non-zero):

* device  — JAX's first device must be a TPU;
* kernels — ``flash_attention`` (InternLM2-1.8B widths, S 512 and 2048, bf16
  and f32), ``ssd_scan`` (Mamba2-130M widths, L 2048, chunk 256) and a
  128-tile ``codegen.build_pallas`` GEMM, compiled with Mosaic and compared
  with ``kernels/ref.py``;
* tune    — a greedy ``TuningSession`` over the attention kernel with a
  Mosaic ``PallasBackend`` (every candidate compiled and verified at full
  extents; the time it ranks by is the TPU cost model's, not the device's);
* serve   — full-width InternLM2-1.8B (random weights from a seed) through
  ``ServeEngine`` with the tuned schedule installed; every request must get
  its tokens, and the first cached decode step must agree with an uncached
  prefill over the prompt plus that token.

``--chips 4`` trains full-width InternLM2-1.8B for 3 steps through
``train_loop.train`` on a (data 2, model 2) mesh, compares the step-0 loss
with an unsharded forward on one chip, and checks that each device holds
about a quarter of the parameter and optimizer bytes.

The last line of standard output is ``{"ok": true, "device": {...}}``.  The
script runs in one process: a TPU belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "runs" / "chip_smoke"
SEED = 0

# stated tolerances: |got - want| <= atol + rtol·|want| elementwise
F32_TOL = 2e-4          # f32 kernels (the tuner's verification tolerance)
BF16_TOL = 2e-2         # bf16 kernels, relative to the output's max |value|
SSD_TOL = 1e-3          # f32 SSD scan against the literal recurrence
LOGITS_TOL = 5e-2       # bf16 serving: cached decode vs uncached prefill,
                        # relative to the logits' max |value|
LOSS_RTOL = 1e-2        # sharded vs one-chip step-0 loss (bf16 compute)


def fail(phase: str, msg: str) -> None:
    print(f"[{phase}] FAIL: {msg}", flush=True)
    sys.exit(1)


def phase_device(chips: int):
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    if d.platform != "tpu":
        fail("device", f"no TPU found (JAX's first device is {d.platform!r})")
    if len(devices) < chips:
        fail("device", f"--chips {chips} needs {chips} TPUs, found "
                       f"{len(devices)}")
    return devices


def _close(phase: str, name: str, got, want, rtol: float, atol: float):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.abs(got - want).max())
    ok = bool(np.isfinite(got).all()) and bool(
        np.allclose(got, want, rtol=rtol, atol=atol))
    print(f"[{phase}] {name}: max err {err:.3e} (atol {atol:.1e}, rtol "
          f"{rtol:.1e}) {'ok' if ok else 'MISMATCH'}", flush=True)
    if not ok:
        fail(phase, f"{name} disagrees with kernels/ref.py")


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import GEMM, Configuration, Tile, codegen
    from repro.core.kernelworkload import ssd_workload
    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED)
    for seq in (512, 2048):
        q = rng.standard_normal((1, 16, seq, 128), np.float32)
        k = rng.standard_normal((1, 8, seq, 128), np.float32)
        v = rng.standard_normal((1, 8, seq, 128), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.attention_ref(q, k, v, causal=True))
        for dtype in (jnp.float32, jnp.bfloat16):
            got = ops.flash_attention(
                jnp.asarray(q, dtype), jnp.asarray(k, dtype),
                jnp.asarray(v, dtype), causal=True, interpret=False)
            if dtype == jnp.float32:
                rtol, atol = F32_TOL, F32_TOL
            else:
                rtol, atol = BF16_TOL, BF16_TOL * float(np.abs(want).max())
            _close("kernels", f"flash_attention S={seq} "
                   f"{jnp.dtype(dtype).name}", got, want, rtol, atol)

    ssd = ssd_workload(heads=24, seq=2048, proj=64, state=128)
    args = ssd.make_args(seed=SEED)
    chunk = Configuration().child(Tile(loops=("l",), sizes=(256,)))
    got = jax.jit(ssd.build(chunk.apply(ssd.nest()), interpret=False))(args)
    with jax.default_matmul_precision("highest"):
        want = ssd.reference(args)
    _close("kernels", "ssd_scan L=2048 chunk=256 f32", got, want, SSD_TOL,
           SSD_TOL)

    tiles = Configuration().child(Tile(loops=("i", "j", "k"),
                                       sizes=(128, 128, 128)))
    gemm = jax.jit(codegen.build_pallas(GEMM, tiles.apply(GEMM.nest()),
                                        interpret=False))
    args = GEMM.make_args(seed=SEED)
    with jax.default_matmul_precision("highest"):
        want = GEMM.reference(args)
    _close("kernels", "build_pallas GEMM 128-tiles f32", gemm(args), want,
           F32_TOL, F32_TOL)


def phase_tune() -> dict:
    from repro.core import PallasBackend, SearchSpace, TuningSession
    from repro.core.kernelworkload import attention_workload

    w = attention_workload(batch=1, heads_q=16, heads_kv=8, seq_q=512,
                           seq_kv=512, head_dim=128)
    space = SearchSpace(root=w.nest(), tile_sizes=(128, 256),
                        max_transformations=2)
    with PallasBackend() as backend:      # Mosaic, full-extent verification
        log = TuningSession(backend, store=False).tune(
            w, space, strategy="greedy", budget=12)
    counts = log.counts()
    print("[tune] " + " ".join(f"{s}={counts.get(s, 0)}" for s in
                               ("ok", "compile_error", "exec_error",
                                "illegal")), flush=True)
    for e in log.experiments:
        if not e.result.ok:
            print(f"[tune]   #{e.number} {e.result.status}: "
                  f"{e.result.note[:200]}", flush=True)
    root = log.baseline
    if not root.result.ok:
        fail("tune", f"root schedule is {root.result.status}: "
                     f"{root.result.note[:500]}")
    best = log.best()
    blocks = w.kernel_params(best.config.apply(w.nest()))
    print(f"[tune] root {w.kernel_params(w.nest())}: cost-model time "
          f"{root.result.time_s:.6e} s", flush=True)
    print(f"[tune] best {blocks} (experiment #{best.number}): cost-model "
          f"time {best.result.time_s:.6e} s — TPU_V5E cost model, not a "
          f"device time", flush=True)
    return {"attention": blocks}


def phase_serve(schedules: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config
    from repro.launch.serve import apply_tuned_schedules
    from repro.models.model import build_model
    from repro.serve.engine import Request, ServeEngine, _install_prefix

    n_req, prompt_len, new_tokens, max_seq = 4, 16, 16, 256
    OUT.mkdir(parents=True, exist_ok=True)
    path = OUT / "kernel_schedules.json"
    path.write_text(json.dumps(schedules))
    cfg, overrides = apply_tuned_schedules(get_config("internlm2_1_8b"),
                                           str(path))
    print(f"[serve] {cfg.name} full width ({cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}), tuned {overrides}",
          flush=True)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(SEED))
    engine = ServeEngine(cfg, params, max_batch=n_req, max_seq=max_seq)
    rng = np.random.default_rng(SEED)
    prompts = rng.integers(1, cfg.vocab_size, (n_req, prompt_len))
    reqs = [Request(prompt=[int(t) for t in p], max_new_tokens=new_tokens)
            for p in prompts]
    out = engine.generate(reqs)
    got = [len(r.out) for r in out]
    print(f"[serve] tokens per request: {got}", flush=True)
    if got != [new_tokens] * n_req:
        fail("serve", f"expected {new_tokens} tokens for each of {n_req} "
                      f"requests, got {got}")

    # the engine's first decode step (cached) against an uncached prefill
    # over prompt + the token it decodes
    prefill = jax.jit(model.prefill)
    tokens = jnp.asarray(prompts, jnp.int32)
    _, pre_caches = prefill(params, {"tokens": tokens})
    first = jnp.asarray([r.out[0] for r in out], jnp.int32)
    caches = _install_prefix(
        model.init_caches(n_req, max_seq, filled=prompt_len), pre_caches,
        max_seq)
    decoded, _ = jax.jit(model.decode_step)(
        params, first[:, None], caches, jnp.full((n_req,), prompt_len,
                                                 jnp.int32))
    want, _ = prefill(params, {"tokens": jnp.concatenate(
        [tokens, first[:, None]], axis=1)})
    want = np.asarray(want[:, -1], np.float32)
    _close("serve", "first decode logits vs uncached prefill",
           decoded[:, -1], want, 0.0,
           LOGITS_TOL * float(np.abs(want).max()))


def phase_train4(devices) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig, host_batch
    from repro.launch.mesh import smoke_mesh
    from repro.models import sharding as sh
    from repro.models.model import build_model
    from repro.optim import OptimizerConfig, init_opt_state
    from repro.train.train_loop import LoopConfig, train

    cfg = get_config("internlm2_1_8b")
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=256, global_batch=8,
                      seed=SEED)
    opt = OptimizerConfig(lr=3e-4, warmup_steps=1, total_steps=3)
    ckpt_dir = OUT / "train_ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)  # else it would be resumed
    loop = LoopConfig(ckpt_dir=str(ckpt_dir), total_steps=3, log_every=1,
                      ckpt_every=1000, seed=SEED)
    print(f"[train] {cfg.name} full width, 3 steps, mesh (data 2, model 2), "
          f"batch {data.global_batch}x{data.seq_len}", flush=True)

    # the reference: the same params and step-0 batch, unsharded, one chip
    model = build_model(cfg)
    one = SingleDeviceSharding(devices[0])
    params = jax.jit(model.init, out_shardings=one)(jax.random.key(SEED))
    batch = {"tokens": jax.device_put(host_batch(data, 0), one)}
    ref_loss = float(jax.jit(model.loss)(params, batch)[0])
    del params, batch
    print(f"[train] one-chip step-0 loss {ref_loss:.6f}", flush=True)

    res = train(cfg, opt, loop, data, mesh=smoke_mesh(2, 2),
                rules=dict(sh.DEFAULT_RULES))
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    losses = dict(res.losses)
    print(f"[train] losses {[(s, round(l, 6)) for s, l in res.losses]}",
          flush=True)
    if sorted(losses) != [0, 1, 2] or not np.isfinite(
            list(losses.values())).all():
        fail("train", f"expected finite losses for steps 0-2, got "
                      f"{res.losses}")
    if abs(losses[0] - ref_loss) > LOSS_RTOL * abs(ref_loss):
        fail("train", f"step-0 loss {losses[0]:.6f} differs from the "
                      f"one-chip forward's {ref_loss:.6f}")
    pspecs = jax.eval_shape(model.init, jax.random.key(SEED))
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        (pspecs, jax.eval_shape(lambda p: init_opt_state(opt, p), pspecs))))
    shares = [b / total for b in res.device_state_bytes]
    print(f"[train] param+optimizer bytes {total} in all; each device "
          f"holds {[round(s, 4) for s in shares]} of them", flush=True)
    if len(shares) != 4 or not all(0.2 <= s <= 0.3 for s in shares):
        fail("train", "params and optimizer state are not spread about "
                      "evenly over the four devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded training phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    compile_cache.enable()

    devices = phase_device(args.chips)
    if args.chips == 4:
        phase_train4(devices)
    else:
        phase_kernels()
        phase_serve(phase_tune())
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
