"""Code generation: (workload × transformed nest) → executable JAX function.

This is the Polly analogue (paper §IV-A): the component that *applies* the
transformation sequence.  Two backends:

* :func:`build_xla` — a tiled XLA:CPU implementation (grid = floor loops in
  schedule order, `lax.fori_loop` + dynamic slices).  Real execution, real
  caches: used by the wallclock measurement backend on this container.
* :func:`build_pallas` — a Pallas TPU kernel: the point band becomes the
  ``BlockSpec`` block shapes (VMEM tiles), floor loops become the grid in
  schedule order, reduction grid dims accumulate through a VMEM scratch
  accumulator.  ``interpret=True`` runs it in the Pallas interpreter on any
  backend; ``interpret=False`` compiles it with Mosaic on a TPU.

Multi-level (stacked) tilings — the paper's missed goal — lower exactly in
both backends via per-loop element spans.  Structures that cannot be expressed
as contiguous windows (tiling a *floor* loop, non-dividing nested spans for
BlockSpecs) raise :class:`CodegenError` and become red nodes, exactly like a
Clang ``-Werror=pass-failed`` compile failure in the paper.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .loopnest import Loop, LoopNest
from .workloads import Workload

# Grid-step budget for the wallclock backend: beyond this the run would exceed
# any reasonable timeout on this container (the paper also kills experiments on
# timeout and marks them invalid, §IV-C).
MAX_WALLCLOCK_GRID_STEPS = 200_000


class CodegenError(Exception):
    """The backend cannot express this schedule (→ red node)."""


@dataclass(frozen=True)
class _Plan:
    """Extracted per-var tiling plan + grid order.

    Multi-level tilings are exact: every non-point loop of a tiled var joins
    the grid, contributing ``index × span`` elements to that var's offset
    (spans are set by Tile.apply), and the single span-1 point loop fixes the
    slice width.  Tiling a *floor* loop (a strided block slice) is the one
    shape dynamic_slice/BlockSpec cannot express → red node.
    """

    tile: dict[str, int]            # var → slice width (innermost tile)
    grid: tuple[tuple[str, int, int], ...]   # (var, trips, span) schedule order
    ext: dict[str, int]
    covered: dict[str, int]         # var → padded extent the grid sweeps


def _extract_plan(w: Workload, nest: LoopNest, max_levels: int = 99) -> _Plan:
    ext = dict(nest.extents)
    per_var: dict[str, list[Loop]] = {}
    for l in nest.loops:
        per_var.setdefault(l.origin, []).append(l)
    tile: dict[str, int] = {}
    tiled_vars: set[str] = set()
    for v, ls in per_var.items():
        points = [l for l in ls if l.is_point]
        if not points:
            tile[v] = ext[v]        # untiled: full extent inside the kernel
            continue
        if len(points) > 1 or points[0].span != 1:
            raise CodegenError(
                f"var {v!r}: tiling of a floor loop yields strided block "
                f"slices, not expressible as a contiguous window")
        levels = sum(1 for l in ls if not l.is_point)
        if levels > max_levels:
            raise CodegenError(
                f"var {v!r} tiled {levels}× (backend limit {max_levels})")
        tile[v] = points[0].trips
        tiled_vars.add(v)
    grid: list[tuple[str, int, int]] = []
    covered = {v: tile[v] for v in tile}
    for l in nest.loops:
        if not l.is_point and l.origin in tiled_vars:
            grid.append((l.origin, l.trips, l.span))
            covered[l.origin] += (l.trips - 1) * l.span
    return _Plan(tile=tile, grid=tuple(grid), ext=ext, covered=covered)


def _letters(w: Workload) -> dict[str, str]:
    return {v: chr(ord("a") + i) for i, v in enumerate(w.loop_order)}


def _tile_einsum(w: Workload, tiles: dict[str, jnp.ndarray]) -> jnp.ndarray:
    lt = _letters(w)
    out_sub = "".join(lt[v] for v in w.out_vars)
    acc = None
    for t in w.terms:
        subs = ",".join("".join(lt[v] for v in vs) for _, vs in t.accesses)
        # f32 operands at f32 precision: Mosaic's default contracts them
        # in one bf16 pass
        r = jnp.einsum(
            f"{subs}->{out_sub}",
            *[tiles[(arr, vs)] for arr, vs in t.accesses],
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        acc = r if acc is None else acc + r
    return acc


def _padded(arr, vs: tuple[str, ...], covered: dict[str, int]) -> jnp.ndarray:
    """Zero-pad ``arr`` up to the covered extents (traceable under ``jit``)."""
    arr = jnp.asarray(arr)
    pads = [(0, covered[v] - arr.shape[d]) for d, v in enumerate(vs)]
    if any(p[1] for p in pads):
        return jnp.pad(arr, pads)
    return arr


def _padded_multi(
    arr: np.ndarray,
    sigs: list[tuple[str, ...]],
    covered: dict[str, int],
):
    """Pad an array accessed under several index signatures (syr2k reads A as
    both A[j,k] and A[i,k]) to the max covered extent any signature requires —
    otherwise dynamic_slice clamps out-of-bounds tiles and reads garbage."""
    pads = []
    for d in range(arr.ndim):
        target = arr.shape[d]
        for vs in sigs:
            target = max(target, covered[vs[d]])
        pads.append((0, target - arr.shape[d]))
    if any(p[1] for p in pads):
        return np.pad(arr, pads)
    return arr


# ---------------------------------------------------------------------------
# XLA:CPU tiled backend (wallclock measurement)
# ---------------------------------------------------------------------------


def build_xla(w: Workload, nest: LoopNest):
    """Returns ``fn(args_dict) -> out`` implementing the schedule with real
    tiled memory traffic.  Raises CodegenError for inexpressible schedules."""
    plan = _extract_plan(w, nest)
    ext = plan.ext
    grid_steps = 1
    for _, trips, _span in plan.grid:
        grid_steps *= trips
    if grid_steps > MAX_WALLCLOCK_GRID_STEPS:
        raise CodegenError(f"grid of {grid_steps} steps exceeds wallclock budget")

    arrays = w.input_arrays()
    out_shape = tuple(plan.covered[v] for v in w.out_vars)

    grid_dims = plan.grid

    @jax.jit
    def inner(padded: dict[str, jnp.ndarray]) -> jnp.ndarray:
        def body(step, out):
            # decompose flat step → per-grid indices, row-major in schedule
            # order; offsets accumulate index × span per var (multi-level)
            off = {v: 0 for v, _, _ in grid_dims}
            rem = step
            for v, trips, span in reversed(grid_dims):
                off[v] = off[v] + (rem % trips) * span
                rem = rem // trips

            tiles = {}
            for t in w.terms:
                for arr, vs in t.accesses:
                    if (arr, vs) in tiles:
                        continue
                    starts = tuple(off.get(v, 0) for v in vs)
                    sizes = tuple(plan.tile[v] for v in vs)
                    tiles[(arr, vs)] = jax.lax.dynamic_slice(padded[arr], starts, sizes)
            part = _tile_einsum(w, tiles)
            ostart = tuple(off.get(v, 0) for v in w.out_vars)
            cur = jax.lax.dynamic_slice(out, ostart, part.shape)
            return jax.lax.dynamic_update_slice(out, cur + part, ostart)

        out = jnp.zeros(out_shape, jnp.float32)
        out = jax.lax.fori_loop(0, grid_steps, body, out)
        out = out[tuple(slice(0, ext[v]) for v in w.out_vars)]
        if w.tri_mode == "lower":
            out = jnp.tril(out)
        elif w.tri_mode == "upper":
            out = jnp.triu(out)
        return out

    sigs: dict[str, list[tuple[str, ...]]] = {}
    for t in w.terms:
        for arr, vs in t.accesses:
            sigs.setdefault(arr, [])
            if vs not in sigs[arr]:
                sigs[arr].append(vs)

    def fn(args: dict) -> jnp.ndarray:
        padded = {
            name: jnp.asarray(
                _padded_multi(np.asarray(args[name]), sigs[name], plan.covered)
            )
            for name in arrays
        }
        return inner(padded)

    return fn


# ---------------------------------------------------------------------------
# Pallas TPU backend (BlockSpec tiling; Mosaic on a TPU, or interpret mode)
# ---------------------------------------------------------------------------


def build_pallas(w: Workload, nest: LoopNest, *, interpret: bool):
    """Pallas kernel for the schedule.  Floor loops → grid (schedule order,
    last dim iterates fastest as on TPU); point band → BlockSpec block shapes;
    reduction grid dims accumulate via VMEM scratch.  The returned
    ``fn(args)`` is traceable, so callers can lower and compile it apart
    from running it."""
    from jax.experimental.pallas import tpu as pltpu

    plan = _extract_plan(w, nest)
    ext = plan.ext
    red_vars = set(w.loop_order) - set(w.out_vars)
    grid_dims = plan.grid
    grid = tuple(trips for _, trips, _s in grid_dims)
    # block-index contributions per var: grid position → span in units of the
    # var's block width (multi-level tilings compose exactly; non-divisible
    # span/tile pairs are not expressible as a BlockSpec window)
    contrib: dict[str, list[tuple[int, int]]] = {}
    for i, (v, _trips, span) in enumerate(grid_dims):
        if span % plan.tile[v] != 0:
            raise CodegenError(
                f"var {v!r}: floor span {span} not a multiple of its block "
                f"width {plan.tile[v]}")
        contrib.setdefault(v, []).append((i, span // plan.tile[v]))
    red_grid = [i for i, (v, _t, _s) in enumerate(grid_dims) if v in red_vars]

    arrays = w.input_arrays()
    acc_list = []
    for t in w.terms:
        for arr, vs in t.accesses:
            if (arr, vs) not in acc_list:
                acc_list.append((arr, vs))

    def _block_index(gids, v):
        total = 0
        for pos, mult in contrib.get(v, ()):
            total = total + gids[pos] * mult
        return total

    def spec_for(vs: tuple[str, ...]) -> pl.BlockSpec:
        block = tuple(plan.tile[v] for v in vs)

        def index_map(*gids, _vs=vs):
            return tuple(_block_index(gids, v) for v in _vs)

        return pl.BlockSpec(block, index_map)

    out_block = tuple(plan.tile[v] for v in w.out_vars)

    def out_index_map(*gids):
        return tuple(_block_index(gids, v) for v in w.out_vars)

    n_in = len(acc_list)

    # The VMEM-scratch accumulator pattern is only valid when every reduction
    # grid dim is minor to (iterates faster than) every output grid dim — then
    # consecutive steps revisit the same output block until it completes.  For
    # other interchanges (reduction dim hoisted outward) we accumulate directly
    # into the (revisited) output block instead: correct, but each grid step
    # pays an HBM round-trip of the output tile — which is exactly the traffic
    # penalty the cost model charges that schedule.
    out_grid = [i for i, (v, _t, _s) in enumerate(grid_dims) if v not in red_vars]
    scratch_ok = not red_grid or not out_grid or min(red_grid) > max(out_grid)

    def kernel(*refs):
        in_refs = refs[:n_in]
        o_ref = refs[n_in]
        acc_ref = refs[n_in + 1]
        tiles = {key: in_refs[i][...] for i, key in enumerate(acc_list)}

        if not red_grid:
            o_ref[...] = _tile_einsum(w, tiles).astype(o_ref.dtype)
            return

        first = functools.reduce(
            jnp.logical_and, [pl.program_id(g) == 0 for g in red_grid]
        )
        if scratch_ok:
            last = functools.reduce(
                jnp.logical_and,
                [pl.program_id(g) == pl.num_programs(g) - 1 for g in red_grid],
            )

            @pl.when(first)
            def _():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            acc_ref[...] += _tile_einsum(w, tiles)

            @pl.when(last)
            def _():
                o_ref[...] = acc_ref[...].astype(o_ref.dtype)
        else:
            @pl.when(first)
            def _():
                o_ref[...] = jnp.zeros_like(o_ref)

            o_ref[...] += _tile_einsum(w, tiles).astype(o_ref.dtype)

    out_shape_padded = tuple(plan.covered[v] for v in w.out_vars)

    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec_for(vs) for _, vs in acc_list],
        out_specs=pl.BlockSpec(out_block, out_index_map),
        out_shape=jax.ShapeDtypeStruct(out_shape_padded, jnp.float32),
        scratch_shapes=[pltpu.VMEM(out_block, jnp.float32)],
        interpret=interpret,
    )

    def fn(args: dict[str, jnp.ndarray]) -> jnp.ndarray:
        ins = []
        for arr, vs in acc_list:
            ins.append(_padded(args[arr], vs, plan.covered))
        out = call(*ins)
        out = out[tuple(slice(0, ext[v]) for v in w.out_vars)]
        if w.tri_mode == "lower":
            out = jnp.tril(out)
        elif w.tri_mode == "upper":
            out = jnp.triu(out)
        return out

    return fn


def vmem_bytes(w: Workload, nest: LoopNest) -> int:
    """VMEM working set claimed by the BlockSpecs of :func:`build_pallas` —
    used to reject tiles that cannot fit (compile failure on real TPU)."""
    plan = _extract_plan(w, nest)
    elem = {(a.array, a.vars): a.elem_bytes for a in nest.accesses}
    default = getattr(w, "elem_bytes", 8)
    total = 0
    seen = set()
    for t in w.terms:
        for arr, vs in t.accesses:
            if (arr, vs) in seen:
                continue
            seen.add((arr, vs))
            n = 1
            for v in vs:
                n *= plan.tile[v]
            total += n * elem.get((arr, vs), default)
    n = 1
    for v in w.out_vars:
        n *= plan.tile[v]
    # out block at its element width + the explicit f32 accumulator scratch
    total += n * elem.get((w.out_array, w.out_vars), default) + n * 4
    return total
