"""Peaks of the chips the benchmark runs on, and the operations and bytes
that each measured piece of work needs, computed from its shapes.

The counts are of the work the algorithm needs at the given sizes, not of
what one implementation happens to do (no XLA ``cost_analysis``, no HLO
counts), so a number stays put whatever implements the step.  Elementwise
work (norms, rotary embedding, softmax, activations) is left out of the
operation counts; matrix products and the state updates of a scan are in.
"""

from __future__ import annotations

#: Published peaks of one chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture page):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def roofline_s(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of the compute bound
    and the memory bound."""
    p = peak(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])


# --------------------------------------------------------------- kernels --


def gemm(m: int, n: int, k: int, in_bytes: int = 2, out_bytes: int = 4):
    """(m, k) @ (k, n): operations and bytes read and written."""
    return 2 * m * n * k, (m * k + k * n) * in_bytes + m * n * out_bytes


def flash_attention(batch: int, heads_q: int, heads_kv: int, seq_q: int,
                    seq_kv: int, head_dim: int, causal: bool = True,
                    in_bytes: int = 2, out_bytes: int = 2):
    """Attention of ``seq_q`` queries over ``seq_kv`` keys (queries aligned
    to the end of the keys when causal): QK^T and PV over the pairs that the
    mask keeps."""
    if causal:
        off = seq_kv - seq_q
        pairs = sum(min(i + 1 + off, seq_kv) for i in range(seq_q))
    else:
        pairs = seq_q * seq_kv
    flops = 4 * batch * heads_q * pairs * head_dim
    nbytes = ((batch * heads_q * seq_q + 2 * batch * heads_kv * seq_kv)
              * head_dim * in_bytes + batch * heads_q * seq_q * head_dim
              * out_bytes)
    return flops, nbytes


def ssd_scan(batch: int, heads: int, seq: int, head_dim: int, state: int,
             chunk: int, groups: int = 1, nbytes_per: int = 4):
    """Chunked state-space-duality scan (Mamba-2): per chunk and head, the
    causal C·B^T scores and their product with x, the chunk's state, its
    contribution to the outputs, and the pass of the state to the next
    chunk."""
    q, n, p = chunk, state, head_dim
    per_chunk = q * (q + 1) // 2 * 2 * (n + p) + 4 * q * p * n + 2 * p * n
    flops = batch * heads * (seq // q) * per_chunk
    nbytes = (2 * batch * seq * heads * head_dim + batch * seq * heads
              + 2 * batch * seq * groups * state) * nbytes_per
    return flops, nbytes


# ---------------------------------------------------------- whole steps --


def _dense_sizes(spec: dict):
    d = spec["hidden_size"]
    h = spec["num_attention_heads"]
    kv = spec["num_key_value_heads"]
    hd = spec.get("head_dim") or d // h
    f = spec["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return d, h, kv, hd, per_layer


def dense_decode_step(spec: dict, batch: int, context: int,
                      weight_bytes: int = 4, cache_bytes: int = 2):
    """One decode step of a dense decoder for ``batch`` sequences, each
    attending over ``context`` cached positions (the new one included)."""
    d, h, kv, hd, per_layer = _dense_sizes(spec)
    n_layers, vocab = spec["num_hidden_layers"], spec["vocab_size"]
    matmul = n_layers * per_layer + d * vocab
    flops = 2 * batch * matmul + n_layers * 4 * batch * h * hd * context
    nbytes = (matmul + batch * d) * weight_bytes + n_layers * batch * (
        context + 1) * 2 * kv * hd * cache_bytes
    return flops, nbytes


def dense_train_step(spec: dict, batch: int, seq: int):
    """Forward and backward of a dense decoder over ``batch`` sequences of
    ``seq`` tokens: 6 operations per parameter of the matrix products and
    per token, plus causal attention three times over (forward, and the
    backward pass's two products); recomputation is not counted."""
    d, h, kv, hd, per_layer = _dense_sizes(spec)
    n_layers, vocab = spec["num_hidden_layers"], spec["vocab_size"]
    matmul = n_layers * per_layer + d * vocab
    pairs = seq * (seq + 1) // 2
    return 6 * matmul * batch * seq + 3 * n_layers * 4 * batch * h * hd * pairs


def _mamba2_sizes(spec: dict):
    d = spec["hidden_size"]
    d_in = spec["expand"] * d
    p = spec["head_dim"]
    h = d_in // p
    g, n = spec["n_groups"], spec["state_size"]
    conv_ch = d_in + 2 * g * n
    w_in = 2 * d_in + 2 * g * n + h
    return d, d_in, h, g, n, p, conv_ch, w_in


def mamba2_decode_step(spec: dict, batch: int, context: int = 0,
                       weight_bytes: int = 4, cache_bytes: int = 2):
    """One decode step of Mamba-2 for ``batch`` sequences: the projections,
    the depthwise convolution over its window, the recurrence on the
    (heads, head_dim, state) state and its read-out, and the head.  The
    work does not grow with ``context``."""
    d, d_in, h, g, n, p, conv_ch, w_in = _mamba2_sizes(spec)
    k = spec["conv_kernel"]
    n_layers, vocab = spec["num_hidden_layers"], spec["vocab_size"]
    per_layer = (2 * d * w_in + 2 * k * conv_ch + 6 * h * p * n + 2 * h * p
                 + 2 * d_in * d)
    flops = batch * (n_layers * per_layer + 2 * d * vocab)
    weights = n_layers * (d * w_in + k * conv_ch + d_in * d) + d * vocab
    state = n_layers * batch * (2 * h * p * n * 4
                                + 2 * (k - 1) * conv_ch * cache_bytes)
    return flops, weights * weight_bytes + state


DECODE_STEPS = {"dense_decoder": dense_decode_step,
                "mamba2": mamba2_decode_step}
