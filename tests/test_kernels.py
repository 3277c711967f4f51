"""Per-kernel allclose sweeps (shapes × dtypes) against the pure-jnp oracles."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (200, 150, 300),
                                   (64, 256, 96), (33, 65, 17)])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_matmul(m, n, k, dtype):
    import jax.numpy as jnp
    dt = jnp.bfloat16 if dtype == "bfloat16" else np.float32
    x = _rand((m, k), np.float32)
    y = _rand((k, n), np.float32)
    got = np.asarray(ops.matmul(x.astype(dt), y.astype(dt),
                                block_m=64, block_n=64, block_k=32,
                                interpret=True),
                     dtype=np.float32)
    want = np.asarray(ref.matmul_ref(x, y))
    tol = 2e-2 if dtype == "bfloat16" else 2e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([32, 64, 128]), st.sampled_from([16, 32, 64]),
       st.sampled_from([16, 32, 128]))
def test_matmul_block_sweep(bm, bn, bk):
    x = _rand((160, 96), np.float32)
    y = _rand((96, 192), np.float32)
    got = np.asarray(ops.matmul(x, y, block_m=bm, block_n=bn, block_k=bk,
                                interpret=True))
    np.testing.assert_allclose(got, np.asarray(ref.matmul_ref(x, y)),
                               rtol=2e-4, atol=1e-3)


@pytest.mark.parametrize("n,k", [(96, 128), (130, 70)])
def test_syr2k(n, k):
    a = _rand((n, k), np.float32)
    b = _rand((n, k), np.float32)
    got = np.asarray(ops.syr2k(a, b, block_i=32, block_j=32, block_k=32,
                               interpret=True))
    np.testing.assert_allclose(got, np.asarray(ref.syr2k_ref(a, b)),
                               rtol=2e-4, atol=1e-3)


@pytest.mark.parametrize("k,m", [(128, 96), (150, 130)])
def test_covariance(k, m):
    d = _rand((k, m), np.float32)
    got = np.asarray(ops.covariance(d, block_i=32, block_j=32, block_k=64,
                                    interpret=True))
    np.testing.assert_allclose(got, np.asarray(ref.covariance_ref(d)),
                               rtol=2e-4, atol=1e-3)


@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (4, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(hq, hkv, causal):
    B, S, D = 2, 128, 64
    q = _rand((B, hq, S, D), np.float32)
    k = _rand((B, hkv, S, D), np.float32)
    v = _rand((B, hkv, S, D), np.float32)
    got = np.asarray(ops.flash_attention(q, k, v, causal=causal,
                                         block_q=32, block_kv=64,
                                         interpret=True))
    want = np.asarray(ref.attention_ref(q, k, v, causal=causal))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_sq_lt_skv():
    """Decode-window case: queries are the last Sq of a longer context."""
    B, H, Sq, Skv, D = 1, 4, 32, 128, 64
    q = _rand((B, H, Sq, D), np.float32)
    k = _rand((B, H, Skv, D), np.float32)
    v = _rand((B, H, Skv, D), np.float32)
    got = np.asarray(ops.flash_attention(q, k, v, causal=True,
                                         block_q=16, block_kv=32,
                                         interpret=True))
    want = np.asarray(ref.attention_ref(q, k, v, causal=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@settings(max_examples=6, deadline=None)
@given(st.sampled_from([16, 32, 64, 128]))
def test_ssd_chunk_sweep(chunk):
    """SSD kernel: the chunk length is a tile size — results must not depend
    on it (the paper's legality invariant for tiling a scan)."""
    BH, L, P, N = 2, 256, 16, 8
    x = (_rand((BH, L, P), np.float32) * 0.1)
    dt = (0.1 + 0.5 * RNG.random((BH, L, 1))).astype(np.float32)
    a = (-0.5 - RNG.random((BH, 1, 1))).astype(np.float32)
    b = (_rand((BH, L, N), np.float32) / np.sqrt(N))
    c = _rand((BH, L, N), np.float32)
    got = np.asarray(ops.ssd_scan(x, dt, a, b, c, chunk=chunk,
                                  interpret=True))
    outs = []
    for h in range(BH):
        yh, _ = ref.ssd_ref_recurrent(
            x[h][:, None, :], dt[h][:, :1], a[h, 0],
            b[h][:, None, :], c[h][:, None, :])
        outs.append(np.asarray(yh)[:, 0, :])
    np.testing.assert_allclose(got, np.stack(outs), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("seq,chunk", [(512, 256), (320, 128)])
def test_ssd_scan_matches_recurrent_at_mamba2_head_widths(seq, chunk):
    """The Mosaic-lowerable SSD kernel (2-D values, prefix sums as
    triangular matmuls) against the literal recurrence at Mamba2-130M's head
    width 64 and state 128, with a sequence the chunk divides and one it
    does not."""
    from repro.core import Configuration, Tile, ssd_workload

    w = ssd_workload(heads=2, seq=seq, proj=64, state=128)
    args = w.make_args(seed=1)
    cfg = Configuration().child(Tile(loops=("l",), sizes=(chunk,)))
    got = np.asarray(w.build(cfg.apply(w.nest()), interpret=True)(args))
    np.testing.assert_allclose(got, np.asarray(w.reference(args)),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("kernel", ["matmul", "flash_attention", "ssd_scan"])
def test_mosaic_off_tpu_raises(kernel):
    """``interpret=False`` compiles with Mosaic; off a TPU that must raise,
    never fall back to the interpreter."""
    import jax

    if jax.default_backend() == "tpu":
        pytest.skip("on a TPU interpret=False is the normal path")
    x = _rand((2, 128, 64), np.float32)
    calls = {
        "matmul": lambda: ops.matmul(x[0], x[0].T, interpret=False),
        "flash_attention": lambda: ops.flash_attention(
            x[None], x[None], x[None], interpret=False),
        "ssd_scan": lambda: ops.ssd_scan(
            x, x[..., :1], x[:, :1, :1], x, x, interpret=False),
    }
    with pytest.raises(ValueError, match="interpret"):
        calls[kernel]()


def test_ssd_chunked_ref_matches_recurrent():
    L, H, P, N = 128, 4, 16, 8
    x = _rand((L, H, P), np.float32) * 0.1
    dt = (0.1 + 0.5 * RNG.random((L, H))).astype(np.float32)
    a = (-0.5 - RNG.random((H,))).astype(np.float32)
    b = _rand((L, 1, N), np.float32) / np.sqrt(N)
    c = _rand((L, 1, N), np.float32)
    y1, h1 = ref.ssd_ref_recurrent(x, dt, a, b, c)
    y2, h2 = ref.ssd_ref_chunked(x, dt, a, b, c, chunk=32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4,
                               atol=1e-4)


def test_decode_attention_ref_consistency():
    """decode oracle == full-attention oracle at the last position."""
    B, Hq, Hkv, S, D = 2, 8, 2, 64, 32
    q = _rand((B, Hq, S, D), np.float32)
    k = _rand((B, Hkv, S, D), np.float32)
    v = _rand((B, Hkv, S, D), np.float32)
    full = np.asarray(ref.attention_ref(q, k, v, causal=True))
    dec = np.asarray(ref.decode_attention_ref(q[:, :, -1], k, v))
    np.testing.assert_allclose(dec, full[:, :, -1], rtol=1e-5, atol=1e-5)
