"""Tunable blocked matmul — the paper's gemm as a Pallas TPU kernel.

The (block_m, block_n, block_k) parameters are exactly the paper's tile sizes:
the autotuner searches them through the same tree search space
(``repro.core.workloads.matmul_workload``).  Defaults below are the TPU-v5e
cost-model optimum found by the tuner (EXPERIMENTS.md §Paper-validation).

Grid order (m, n, k) with k minor: the f32 accumulator lives in VMEM scratch
across the k-phase and the output block is written once — the "scratch_ok"
schedule of repro.core.codegen.  An (n, m, k) interchange is the same kernel
with swapped index maps; hoisting k outward is expressible but pays an output
round-trip per step, which the cost model charges accordingly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype: jnp.dtype | None = None,
    interpret: bool,
) -> jnp.ndarray:
    """``x @ y`` with explicit VMEM tiling.  Shapes must divide the blocks
    (the ``ops`` wrapper pads); accumulation is f32."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x.shape, y.shape, (bm, bn, bk))
    out_dtype = out_dtype or x.dtype
    return pl.pallas_call(
        _matmul_kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, y)
