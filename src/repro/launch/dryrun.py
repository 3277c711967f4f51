"""Multi-pod dry-run: AOT-lower + compile every (arch × shape × mesh) cell.

For each cell this script:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. lowers + compiles the full step (train_step / prefill / decode_step) with
     scan-over-layers and explicit in_shardings,
  3. prints ``compiled.memory_analysis()`` (proves the cell fits HBM) and
     ``compiled.cost_analysis()``,
  4. additionally lowers one layer-period per scanned group with identical
     shardings and stitches ``total = full + (reps−1)·layer`` (XLA counts a
     while body once — see roofline/analysis.py),
  5. writes a JSON roofline record to --out.

Usage:
  python -m repro.launch.dryrun --arch qwen1_5_32b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out benchmarks/results/dryrun
"""

import os

# The 512 placeholder host devices must be forced before the first jax
# import below — appended to any user-set XLA_FLAGS, never clobbering them.
_HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=512"
if _HOST_DEVICES_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        f"{os.environ.get('XLA_FLAGS', '')} {_HOST_DEVICES_FLAG}".strip())

import argparse
import dataclasses
import functools
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, arch_ids, get_config, shape_cells
from repro.launch.mesh import make_production_mesh
from repro.models import sharding as sh
from repro.models.model import build_model, count_params_from_specs, layer_groups
from repro.optim import OptimizerConfig, init_opt_state
from repro.roofline.analysis import RooflineReport, cost_summary, stitch
from repro.train.steps import (batch_axes, input_specs, make_train_step,
                               state_shardings)


def _axes_is_leaf(x):
    return isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)


def decode_rules(cfg, mesh):
    """Shard KV over heads when they divide the model axis, else over sequence
    (flash-decode style); tiny-batch cells replicate the batch axis."""
    rules = dict(sh.DEFAULT_RULES)
    msize = mesh.shape.get("model", 1)
    heads_ok = cfg.n_kv_heads > 0 and cfg.n_kv_heads % msize == 0
    if heads_ok and not cfg.use_mla:
        rules["kv_heads"] = "model"
        rules["kv_seq"] = None
    else:
        rules["kv_heads"] = None
        rules["kv_seq"] = "model"
    return rules


def cell_rules(cfg, cell, mesh):
    rules = decode_rules(cfg, mesh) if cell.kind == "decode" else dict(sh.DEFAULT_RULES)
    dsize = 1
    for ax in ("pod", "data"):
        dsize *= mesh.shape.get(ax, 1)
    if cell.global_batch < dsize:
        rules["batch"] = None
    return rules


def _shardings_for(tree_axes, tree_specs=None):
    """Axes tree → NamedShardings.  With ``tree_specs`` (matching tree of
    ShapeDtypeStructs) non-divisible dims fall back to replicated — explicit
    pjit argument shardings require exact divisibility."""
    if tree_specs is None:
        return jax.tree.map(lambda a: sh.named_sharding(*a), tree_axes,
                            is_leaf=_axes_is_leaf)
    return jax.tree.map(
        lambda a, s: sh.named_sharding_for(s.shape, *a),
        tree_axes, tree_specs, is_leaf=_axes_is_leaf)


def lower_cell(arch: str, shape_name: str, mesh_kind: str,
               opt_cfg: OptimizerConfig | None = None, verbose: bool = True,
               dist=None):
    """AOT-lower + compile one cell.  ``dist`` (a core.distconfig.DistConfig)
    overrides the distributed schedule — the §Perf hillclimb hook."""
    import dataclasses as _dc

    cfg = get_config(arch)
    cell = shape_cells(cfg)[shape_name]
    if cell is None:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "skip": "long_500k requires sub-quadratic sequence mixing "
                        "(pure full-attention arch; per-assignment skip)"}
    microbatches = 1
    if dist is not None:
        attn_chunk = 0
        expert_dtype = ""
        for f in dist.flags:
            if f.startswith("attn_chunk="):
                attn_chunk = int(f.split("=")[1])
            if f.startswith("expert_dtype="):
                expert_dtype = f.split("=")[1]
        cfg = _dc.replace(cfg, remat=dist.remat,
                          capacity_factor=dist.moe_capacity,
                          attn_q_chunk=attn_chunk,
                          expert_dtype=expert_dtype)
        microbatches = dist.microbatches
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size
    model = build_model(cfg)
    opt_cfg = opt_cfg or OptimizerConfig(
        factored_experts=cfg.n_experts >= 256,
        moments_dtype="bfloat16" if cfg.n_experts >= 256 else "float32")

    rules = cell_rules(cfg, cell, mesh)
    if dist is not None:
        rules = dist.rules(rules)

    t0 = time.time()
    with sh.scope(mesh, rules):
        key = jax.random.key(0)
        pspecs = jax.eval_shape(lambda: model.init(key))
        pshard = _shardings_for(model.axes(), pspecs)
        bspecs = input_specs(cfg, cell)
        bshard = _shardings_for(batch_axes(cfg, cell), bspecs)

        if cell.kind == "train":
            ospecs = jax.eval_shape(
                functools.partial(init_opt_state, opt_cfg), pspecs)
            _, oshard = state_shardings(model, opt_cfg, pspecs)
            step = make_train_step(model, opt_cfg, microbatches=microbatches)
            jitted = jax.jit(step,
                             in_shardings=(pshard, oshard, bshard),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(pspecs, ospecs, bspecs)
        elif cell.kind == "prefill":
            jitted = jax.jit(model.prefill, in_shardings=(pshard, bshard))
            lowered = jitted.lower(pspecs, bspecs)
        else:   # decode
            cspecs = jax.eval_shape(
                functools.partial(model.init_caches, cell.global_batch,
                                  cell.seq_len))
            cshard = _shardings_for(model.cache_axes(), cspecs)
            tok = jax.ShapeDtypeStruct((cell.global_batch, 1), jnp.int32)
            pos = jax.ShapeDtypeStruct((cell.global_batch,), jnp.int32)
            tshard = sh.named_sharding("batch", None)
            posshard = sh.named_sharding("batch")
            jitted = jax.jit(model.decode_step,
                             in_shardings=(pshard, tshard, cshard, posshard),
                             donate_argnums=(2,))
            lowered = jitted.lower(pspecs, tok, cspecs, pos)

        compiled = lowered.compile()
        full = cost_summary(compiled, chips, while_trips=1)
        mem = compiled.memory_analysis()
        if verbose:
            print(f"[{arch}·{shape_name}·{mesh_kind}] memory_analysis:", mem)
            ca = compiled.cost_analysis() or {}
            print(f"[{arch}·{shape_name}·{mesh_kind}] cost_analysis: "
                  f"flops={ca.get('flops', 0):.3e} "
                  f"bytes={ca.get('bytes accessed', 0):.3e}")

        # ---- per-layer stitching ------------------------------------------
        stitched = dict(full)
        groups = layer_groups(cfg)
        for g, (period, reps) in enumerate(groups):
            if reps <= 1:
                continue
            lcost = _lower_period_cost(model, cfg, cell, pspecs, g, chips)
            stitched = stitch(stitched, lcost, reps)

    n_params = count_params_from_specs(cfg)
    n_active = count_params_from_specs(cfg, active_only=True)
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    if cell.kind == "train":
        model_flops = 6.0 * n_active * tokens
    else:
        model_flops = 2.0 * n_active * tokens

    rep = RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_kind, chips=chips,
        flops=stitched["flops"], hbm_bytes=stitched["hbm_bytes"],
        wire_bytes=stitched["wire_bytes"],
        argument_bytes=full["argument_bytes"], temp_bytes=full["temp_bytes"],
        output_bytes=full["output_bytes"], model_flops_total=model_flops,
        notes=f"params={n_params:.3e} active={n_active:.3e} "
              f"compile_s={time.time()-t0:.1f}")
    if verbose:
        print(f"[{arch}·{shape_name}·{mesh_kind}] roofline: "
              f"compute={rep.compute_s*1e3:.2f}ms memory={rep.memory_s*1e3:.2f}ms "
              f"collective={rep.collective_s*1e3:.2f}ms dominant={rep.dominant} "
              f"roofline_frac={rep.roofline_fraction:.3f}")
    return rep.to_dict()


def _lower_period_cost(model, cfg, cell, pspecs, g, chips):
    """Per-device cost of one layer-period (same shardings as the full step).

    Train: fwd+bwd (with the config's remat policy — matching what the scan
    body costs in the full step).  Prefill: fwd.  Decode: the decode path
    against this cell's cache (append + attend), which is a completely
    different cost profile than the train body.
    """
    import functools as ft

    from repro.models.blocks import (apply_block, block_axes,
                                     cache_axes as bcache_axes, init_cache)

    groups = layer_groups(cfg)
    period, reps = groups[g]
    stack_specs = pspecs["stacks"][g]
    period_specs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), stack_specs)
    paxes = {f"b{i}": block_axes(kind, cfg) for i, kind in enumerate(period)}
    pshard = _shardings_for(paxes, period_specs)

    B = cell.global_batch
    S = cell.seq_len if cell.kind != "decode" else 1
    x_spec = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.dtype(cfg.dtype))
    x_shard = sh.named_sharding("batch", "seq", "embed")

    if cell.kind == "decode":
        cache_specs = {
            f"b{i}": jax.eval_shape(ft.partial(
                init_cache, kind, cfg, B, cell.seq_len, enc_seq=cfg.enc_seq))
            for i, kind in enumerate(period)}
        caxes = {f"b{i}": bcache_axes(kind, cfg)
                 for i, kind in enumerate(period)}
        cshard = _shardings_for(caxes, cache_specs)

        def step(pp, x, pc):
            positions = jnp.full((B, 1), cell.seq_len // 2, jnp.int32)
            ncs = {}
            for i, kind in enumerate(period):
                x, nc, _ = apply_block(kind, x, pp[f"b{i}"], cfg, positions,
                                       cache=pc[f"b{i}"])
                ncs[f"b{i}"] = nc
            return x, ncs

        lowered = jax.jit(step, in_shardings=(pshard, x_shard, cshard),
                          donate_argnums=(2,)).lower(
            period_specs, x_spec, cache_specs)
        return cost_summary(lowered.compile(), chips, while_trips=1)

    # whisper decoder blocks need the cross-attention K/V even in train mode
    cross_specs = {}
    cross_shard = {}
    for i, kind in enumerate(period):
        if kind == "dec":
            kv = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.n_kv_heads, cfg.head_dim),
                jnp.dtype(cfg.dtype))
            cross_specs[f"b{i}"] = {"cross_k": kv, "cross_v": kv}
            kvs = sh.named_sharding_for(kv.shape, "batch", None, "kv_heads",
                                        None)
            cross_shard[f"b{i}"] = {"cross_k": kvs, "cross_v": kvs}
        else:
            cross_specs[f"b{i}"] = None
            cross_shard[f"b{i}"] = None

    def fwd(pp, x, cc):
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        for i, kind in enumerate(period):
            x, _, _ = apply_block(kind, x, pp[f"b{i}"], cfg, positions,
                                  cache=cc[f"b{i}"])
        return jnp.mean(x.astype(jnp.float32))

    if cell.kind == "train":
        fwd_ = jax.checkpoint(fwd) if cfg.remat != "none" else fwd
        fn = jax.grad(fwd_, argnums=(0, 1))
    else:
        fn = fwd
    lowered = jax.jit(fn, in_shardings=(pshard, x_shard, cross_shard)).lower(
        period_specs, x_spec, cross_specs)
    return cost_summary(lowered.compile(), chips, while_trips=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", type=str, default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default="benchmarks/results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = arch_ids() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                path = outdir / f"{arch}__{shape}__{mk}.json"
                if path.exists() and not args.force:
                    print(f"skip (cached): {path.name}")
                    continue
                try:
                    rec = lower_cell(arch, shape, mk)
                    path.write_text(json.dumps(rec, indent=1))
                    print(f"wrote {path.name}")
                except Exception as e:     # noqa: BLE001
                    failures.append((arch, shape, mk, f"{type(e).__name__}: {e}"))
                    print(f"FAIL {arch}·{shape}·{mk}: {type(e).__name__}: {e}",
                          file=sys.stderr)
    if failures:
        print(f"\n{len(failures)} failures:", file=sys.stderr)
        for f in failures:
            print("  ", f, file=sys.stderr)
        sys.exit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
