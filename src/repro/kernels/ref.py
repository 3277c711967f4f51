"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` is the semantic ground truth the kernels are asserted against
(in interpret mode on CPU, compiled with Mosaic on a TPU).  They are
deliberately written as straight-line jnp — no blocking, no tricks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """C[i,j] = Σ_k A[i,k]·B[k,j] (PolyBench gemm core, f32 accumulation)."""
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def syr2k_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Lower-triangular C[i,j] = Σ_k A[j,k]B[i,k] + B[j,k]A[i,k] (j ≤ i)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    full = b @ a.T + a @ b.T
    return jnp.tril(full)


def covariance_ref(data: jnp.ndarray) -> jnp.ndarray:
    """Upper-triangular cov[i,j] = Σ_k data[k,i]·data[k,j] (j ≥ i), with the
    mean already subtracted (PolyBench subtracts the column mean first; the
    tunable nest is the rank-k update)."""
    d = data.astype(jnp.float32)
    return jnp.triu(d.T @ d)


def attention_ref(
    q: jnp.ndarray,          # (B, Hq, Sq, D)
    k: jnp.ndarray,          # (B, Hkv, Skv, D)
    v: jnp.ndarray,          # (B, Hkv, Skv, D)
    causal: bool = True,
    scale: float | None = None,
) -> jnp.ndarray:
    """Grouped-query softmax attention, f32 softmax."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kf = jnp.repeat(kf, group, axis=1)
    vf = jnp.repeat(vf, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    if causal:
        # last Sq queries of a length-Skv context
        Skv = k.shape[2]
        qpos = jnp.arange(Sq)[:, None] + (Skv - Sq)
        kpos = jnp.arange(Skv)[None, :]
        s = jnp.where(kpos <= qpos, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def decode_attention_ref(
    q: jnp.ndarray,          # (B, Hq, D) — one query token
    k: jnp.ndarray,          # (B, Hkv, S, D)
    v: jnp.ndarray,          # (B, Hkv, S, D)
    length: jnp.ndarray | None = None,   # (B,) valid KV lengths
) -> jnp.ndarray:
    B, Hq, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    group = Hq // Hkv
    scale = 1.0 / (D ** 0.5)
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=1)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32) * scale, kf)
    if length is not None:
        mask = jnp.arange(S)[None, None, :] < length[:, None, None]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, vf).astype(q.dtype)


def ssd_ref_recurrent(
    x: jnp.ndarray,          # (L, H, P)
    dt: jnp.ndarray,         # (L, H)      — softplus already applied
    a: jnp.ndarray,          # (H,)        — negative decay rates
    b: jnp.ndarray,          # (L, G, N)
    c: jnp.ndarray,          # (L, G, N)
    h0: jnp.ndarray | None = None,   # (H, P, N)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mamba-2 SSD as the literal recurrence (the slowest, most obviously
    correct form).  h_t = exp(dt_t·a)·h_{t-1} + dt_t·(x_t ⊗ b_t);
    y_t = h_t · c_t.  Heads are grouped over B/C (G groups)."""
    L, H, P = x.shape
    G, N = b.shape[1], b.shape[2]
    hpg = H // G
    if h0 is None:
        h0 = jnp.zeros((H, P, N), jnp.float32)

    def step(h, inputs):
        xt, dtt, bt, ct = inputs
        decay = jnp.exp(dtt * a)[:, None, None]                 # (H,1,1)
        bg = jnp.repeat(bt, hpg, axis=0)                        # (H,N)
        cg = jnp.repeat(ct, hpg, axis=0)
        h = decay * h + (dtt[:, None] * xt)[..., None] * bg[:, None, :]
        y = jnp.einsum("hpn,hn->hp", h, cg)
        return h, y

    h, ys = jax.lax.scan(step, h0.astype(jnp.float32),
                         (x.astype(jnp.float32), dt.astype(jnp.float32),
                          b.astype(jnp.float32), c.astype(jnp.float32)))
    return ys.astype(x.dtype), h


def ssd_ref_chunked(
    x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
    b: jnp.ndarray, c: jnp.ndarray, chunk: int = 64,
    h0: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked (state-space dual) form — same math, O(L·chunk) attention-like
    intra-chunk term plus inter-chunk state passing.  This is the blocked
    algorithm the Pallas kernel implements; ``chunk`` is a *tile size* in the
    paper's search space."""
    L, H, P = x.shape
    G, N = b.shape[1], b.shape[2]
    hpg = H // G
    assert L % chunk == 0
    nchunks = L // chunk
    xf = x.astype(jnp.float32).reshape(nchunks, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(nchunks, chunk, H)
    bf = jnp.repeat(b.astype(jnp.float32), hpg, axis=1).reshape(nchunks, chunk, H, N)
    cf = jnp.repeat(c.astype(jnp.float32), hpg, axis=1).reshape(nchunks, chunk, H, N)
    if h0 is None:
        h0 = jnp.zeros((H, P, N), jnp.float32)

    def chunk_step(h, inputs):
        xc, dtc, bc, cc = inputs          # (chunk,H,P),(chunk,H),(chunk,H,N)×2
        la = dtc * a[None, :]             # log-decay per step (chunk,H)
        cum = jnp.cumsum(la, axis=0)      # (chunk,H) inclusive
        # intra-chunk: y_t += Σ_{s<=t} exp(cum_t - cum_s) dt_s (c_t·b_s) x_s
        mask = jnp.tril(jnp.ones((chunk, chunk), bool))
        seg = cum[:, None, :] - cum[None, :, :]                 # (t,s,H)
        decay = jnp.exp(jnp.where(mask[:, :, None], seg, -1e30))
        scores = jnp.einsum("thn,shn->tsh", cc, bc) * decay
        y = jnp.einsum("tsh,sh,shp->thp", scores, dtc, xc)
        # inter-chunk: contribution of incoming state
        y += jnp.einsum("thn,hpn,th->thp", cc, h, jnp.exp(cum))
        # state update: h' = exp(total)·h + Σ_s exp(total-cum_s) dt_s b_s⊗x_s
        total = cum[-1]                   # (H,)
        w = jnp.exp(total[None, :] - cum) * dtc                 # (chunk,H)
        h = jnp.exp(total)[:, None, None] * h + jnp.einsum(
            "sh,shn,shp->hpn", w, bc, xc)
        return h, y

    h, ys = jax.lax.scan(chunk_step, h0.astype(jnp.float32),
                         (xf, dtf, bf, cf))
    return ys.reshape(L, H, P).astype(x.dtype), h
