"""Tunable covariance Pallas kernel — PolyBench covariance's rank-k update
(§V-C): cov[i,j] = Σ_k data[k,i]·data[k,j] for j ≥ i (upper triangular).

Note the transposed access pattern data[k,i]: the reduction runs over the
*rows* of data, so the natural MXU mapping is dataᵀ·data with the k-dim as the
contraction — the kernel reads (block_k, block_i) column panels, which is why
the tuner prefers larger block_k here than for gemm (EXPERIMENTS.md)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cov_kernel(di_ref, dj_ref, o_ref, acc_ref, *, block_i, block_j):
    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # cov_tile[i,j] += data[:,i]^T · data[:,j]
    acc_ref[...] += jnp.dot(
        di_ref[...].T, dj_ref[...], preferred_element_type=jnp.float32
    )

    gi = pl.program_id(0) * block_i
    gj = pl.program_id(1) * block_j

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        rows = gi + jax.lax.broadcasted_iota(jnp.int32, (block_i, block_j), 0)
        cols = gj + jax.lax.broadcasted_iota(jnp.int32, (block_i, block_j), 1)
        o_ref[...] = jnp.where(cols >= rows, acc_ref[...], 0.0).astype(o_ref.dtype)


def covariance(
    data: jnp.ndarray,
    *,
    block_i: int = 256,
    block_j: int = 256,
    block_k: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    k, m = data.shape
    bi, bj, bk = min(block_i, m), min(block_j, m), min(block_k, k)
    assert m % bi == 0 and m % bj == 0 and k % bk == 0
    kern = functools.partial(_cov_kernel, block_i=bi, block_j=bj)
    return pl.pallas_call(
        kern,
        grid=(m // bi, m // bj, k // bk),
        in_specs=[
            pl.BlockSpec((bk, bi), lambda i, j, l: (l, i)),
            pl.BlockSpec((bk, bj), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        interpret=interpret,
    )(data, data)
