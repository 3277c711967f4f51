"""The operation and byte counts against counts made by hand, and the peak
table."""

from __future__ import annotations

import itertools

import pytest

from bench import roofline

DENSE = dict(hidden_size=8, intermediate_size=16, num_attention_heads=2,
             num_key_value_heads=1, num_hidden_layers=3, vocab_size=10)
MAMBA = dict(hidden_size=4, num_hidden_layers=2, vocab_size=10,
             state_size=3, expand=2, head_dim=2, n_groups=1, conv_kernel=4)


def test_gemm():
    assert roofline.gemm(2, 3, 4, in_bytes=2, out_bytes=4) == (
        2 * 2 * 3 * 4, (2 * 4 + 4 * 3) * 2 + 2 * 3 * 4)


@pytest.mark.parametrize("sq,skv", [(5, 5), (3, 7)])
def test_flash_attention_counts_the_pairs_the_mask_keeps(sq, skv):
    kept = sum(1 for i, j in itertools.product(range(sq), range(skv))
               if j <= i + skv - sq)
    flops, nbytes = roofline.flash_attention(2, 4, 2, sq, skv, 8)
    assert flops == 4 * 2 * 4 * kept * 8
    assert nbytes == (2 * 4 * sq + 2 * 2 * 2 * skv) * 8 * 2 + 2 * 4 * sq * 8 * 2
    assert roofline.flash_attention(1, 1, 1, sq, skv, 1, causal=False)[0] \
        == 4 * sq * skv


def test_ssd_scan():
    # one chunk of 4, state 3, head_dim 2: C·B^T and its product with x
    # over 10 causal pairs, the chunk state and its read-out, the hand-off
    per_chunk = 10 * 2 * (3 + 2) + 4 * 4 * 2 * 3 + 2 * 2 * 3
    assert roofline.ssd_scan(1, 5, 8, 2, 3, 4)[0] == 5 * 2 * per_chunk


def test_dense_decode_step():
    d, f, h, kv, hd, layers, v = 8, 16, 2, 1, 4, 3, 10
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    matmul = layers * per_layer + d * v
    flops, nbytes = roofline.dense_decode_step(DENSE, batch=2, context=6)
    assert flops == 2 * 2 * matmul + layers * 4 * 2 * h * hd * 6
    assert nbytes == (matmul + 2 * d) * 4 + layers * 2 * 7 * 2 * kv * hd * 2


def test_dense_train_step():
    d, f, h, kv, hd, layers, v = 8, 16, 2, 1, 4, 3, 10
    matmul = layers * (d * h * hd + 2 * d * kv * hd + h * hd * d
                       + 3 * d * f) + d * v
    assert roofline.dense_train_step(DENSE, batch=2, seq=5) == (
        6 * matmul * 10 + 3 * layers * 4 * 2 * h * hd * 15)


def test_mamba2_decode_step():
    d, d_in, p, n, k, layers, v = 4, 8, 2, 3, 4, 2, 10
    h, conv_ch = d_in // p, d_in + 2 * n
    w_in = 2 * d_in + 2 * n + h
    per_layer = (2 * d * w_in + 2 * k * conv_ch + 6 * h * p * n + 2 * h * p
                 + 2 * d_in * d)
    flops, nbytes = roofline.mamba2_decode_step(MAMBA, batch=3)
    assert flops == 3 * (layers * per_layer + 2 * d * v)
    weights = layers * (d * w_in + k * conv_ch + d_in * d) + d * v
    state = layers * 3 * (2 * h * p * n * 4 + 2 * (k - 1) * conv_ch * 2)
    assert nbytes == weights * 4 + state
    assert roofline.mamba2_decode_step(MAMBA, 3, context=500) == (flops,
                                                                   nbytes)


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    assert roofline.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peak("cpu")
    # 1 GFLOP against 1 GB: memory bound; 1 TFLOP against 1 GB: compute
    assert roofline.roofline_s(1e9, 1e9, "TPU v5 lite") == 1e9 / 819e9
    assert roofline.roofline_s(1e12, 1e9, "TPU v5 lite") == 1e12 / 197e12
