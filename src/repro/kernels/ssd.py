"""Mamba-2 SSD (state-space duality) chunked-scan Pallas kernel.

The SSD algorithm (Dao & Gu, arXiv:2405.21060) is itself a *tiling* of a linear
recurrence: the chunk length is a tile size trading intra-chunk matmul work
(MXU-friendly, quadratic in chunk) against inter-chunk sequential state passing
— i.e. the paper's search space applies to the chunk length directly, which is
why mamba2 is one of the §Perf hillclimb candidates.

Kernel layout: grid = (batch·head, n_chunks) with the chunk dim sequential
("arbitrary" semantics — it carries the (N, P) state in VMEM scratch).  Each
step does three MXU contractions (CBᵀ scores, score·x, state update) on
(chunk × N/P) tiles.

Every value in the kernel body is 2-D — per-step quantities are ``(chunk, 1)``
columns or ``(1, chunk)`` rows — because Mosaic lowers neither ``cumsum`` nor
the dynamic slices that 1-D vector indexing produces.  The inclusive prefix
sum of the log-decay is a product with a triangular 0/1 matrix.  Every
contraction of f32 values runs at ``HIGHEST`` precision: Mosaic's default
contracts f32 operands in one bf16 pass, which the decays and an f32 caller
cannot afford.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref,
                *, chunk):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)        # (chunk, P)
    dt = dt_ref[0].astype(jnp.float32)      # (chunk, 1)
    a = a_ref[0].astype(jnp.float32)        # (1, 1) decay rate (negative)
    b = b_ref[0].astype(jnp.float32)        # (chunk, N)
    c = c_ref[0].astype(jnp.float32)        # (chunk, N)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = s_idx <= t_idx                    # (t, s): s ≤ t
    la = dt * a                             # (chunk, 1) log-decay
    # the same values as a row: only the diagonal term of each column is
    # nonzero, so the reduction is exact
    la_row = jnp.sum(jnp.where(s_idx == t_idx, la, 0.0), axis=0,
                     keepdims=True)         # (1, chunk)
    dt_row = jnp.sum(jnp.where(s_idx == t_idx, dt, 0.0), axis=0,
                     keepdims=True)         # (1, chunk)
    # inclusive prefix sums: cum[t] = Σ_{s≤t} la[s], as a column and a row
    cum = jnp.dot(tri.astype(jnp.float32), la, precision=_HI,
                  preferred_element_type=jnp.float32)            # (chunk, 1)
    cum_row = jnp.dot(la_row, (t_idx <= s_idx).astype(jnp.float32),
                      precision=_HI,
                      preferred_element_type=jnp.float32)        # (1, chunk)
    total = jnp.dot(la_row, jnp.ones((chunk, 1), jnp.float32), precision=_HI,
                    preferred_element_type=jnp.float32)          # (1, 1)
    # intra-chunk lower-triangular decay kernel (masked before exp — the
    # upper entries have positive exponents that overflow)
    decay = jnp.exp(jnp.where(tri, cum - cum_row, -1e30))
    scores = jnp.dot(c, b.T, precision=_HI,
                     preferred_element_type=jnp.float32) * decay
    y = jnp.dot(scores * dt_row, x, precision=_HI,
                preferred_element_type=jnp.float32)            # (chunk, P)
    # inter-chunk: incoming state contribution
    h = h_ref[...]                                             # (N, P)
    y += jnp.exp(cum) * jnp.dot(c, h, precision=_HI,
                                preferred_element_type=jnp.float32)
    # state update: h' = exp(total)·h + Σ_s exp(total-cum_s)·dt_s·b_s⊗x_s
    w = jnp.exp(total - cum) * dt                              # (chunk, 1)
    # (1, 1) → (1, P) first: Mosaic cannot broadcast over sublanes and
    # lanes in one step
    carry = jnp.exp(jnp.broadcast_to(total, (1, h.shape[1])))  # (1, P)
    h_ref[...] = carry * h + jnp.dot(
        (b * w).T, x, precision=_HI, preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan(
    x: jnp.ndarray,          # (BH, L, P)   batch·heads flattened
    dt: jnp.ndarray,         # (BH, L, 1)
    a: jnp.ndarray,          # (BH, 1, 1)   per-head decay rate
    b: jnp.ndarray,          # (BH, L, N)   already head-grouped
    c: jnp.ndarray,          # (BH, L, N)
    *,
    chunk: int = 64,
    interpret: bool,
) -> jnp.ndarray:
    BH, L, P = x.shape
    N = b.shape[-1]
    ch = min(chunk, L)
    # Non-divisible chunk: pad the sequence axis with zeros.  The scan is
    # causal left-to-right, so zero-padded trailing steps (x=b=c=0, dt=0)
    # never influence y[:, :L]; the padded rows are sliced off the output.
    l_p = -(-L // ch) * ch
    if l_p != L:
        pad = ((0, 0), (0, l_p - L), (0, 0))
        x, dt, b, c = (jnp.pad(t, pad) for t in (x, dt, b, c))
    kern = functools.partial(_ssd_kernel, chunk=ch)
    out = pl.pallas_call(
        kern,
        grid=(BH, l_p // ch),
        in_specs=[
            pl.BlockSpec((1, ch, P), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, ch, 1), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, 1, 1), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, ch, N), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, ch, N), lambda h, i: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, ch, P), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, l_p, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, b, c)
    return out[:, :L]
