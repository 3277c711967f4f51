"""Tunable syr2k Pallas kernel — PolyBench's symmetric rank-2k update (§V-B).

C[i,j] = Σ_k A[j,k]·B[i,k] + B[j,k]·A[i,k] for j ≤ i.  The triangular output is
handled the way Polly handles non-rectangular nests: full-rectangle tiles with
the strictly-upper part masked in the final write — block (i,j) tiles entirely
above the diagonal are dead (their mask is all-zero); a production grid would
skip them, here the mask keeps the index maps affine, and the cost model's
triangular scale (0.5) accounts for the saved work.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _syr2k_kernel(a_i_ref, b_i_ref, a_j_ref, b_j_ref, o_ref, acc_ref, *, block_i, block_j):
    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # C_tile[i,j] += B_i[i,k]·A_j[j,k]^T + A_i[i,k]·B_j[j,k]^T
    acc_ref[...] += jnp.dot(
        b_i_ref[...], a_j_ref[...].T, preferred_element_type=jnp.float32
    ) + jnp.dot(
        a_i_ref[...], b_j_ref[...].T, preferred_element_type=jnp.float32
    )

    gi = pl.program_id(0) * block_i
    gj = pl.program_id(1) * block_j

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        rows = gi + jax.lax.broadcasted_iota(jnp.int32, (block_i, block_j), 0)
        cols = gj + jax.lax.broadcasted_iota(jnp.int32, (block_i, block_j), 1)
        o_ref[...] = jnp.where(cols <= rows, acc_ref[...], 0.0).astype(o_ref.dtype)


def syr2k(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_i: int = 256,
    block_j: int = 256,
    block_k: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    n, k = a.shape
    assert b.shape == (n, k)
    bi, bj, bk = min(block_i, n), min(block_j, n), min(block_k, k)
    assert n % bi == 0 and n % bj == 0 and k % bk == 0
    import functools

    kern = functools.partial(_syr2k_kernel, block_i=bi, block_j=bj)
    return pl.pallas_call(
        kern,
        grid=(n // bi, n // bj, k // bk),
        in_specs=[
            pl.BlockSpec((bi, bk), lambda i, j, l: (i, l)),   # A[i,:]
            pl.BlockSpec((bi, bk), lambda i, j, l: (i, l)),   # B[i,:]
            pl.BlockSpec((bj, bk), lambda i, j, l: (j, l)),   # A[j,:]
            pl.BlockSpec((bj, bk), lambda i, j, l: (j, l)),   # B[j,:]
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        interpret=interpret,
    )(a, b, a, b)
