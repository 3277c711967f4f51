"""Per-architecture smoke tests: reduced config of the same family, one
loss+grad step and one prefill+decode step on CPU, asserting shapes + finite."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import arch_ids, get_config, shape_cells, SHAPES
from repro.models.model import build_model, count_params_from_specs

RNG = np.random.default_rng(0)
B, S = 2, 32


def _batch(cfg):
    batch = {"tokens": jnp.asarray(
        RNG.integers(0, cfg.vocab_size, (B, S + 1)), jnp.int32)}
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            RNG.standard_normal((B, cfg.num_patches, cfg.d_model)), jnp.float32)
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            RNG.standard_normal((B, cfg.enc_seq, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", arch_ids())
def test_smoke_train_and_serve(arch):
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    batch = _batch(cfg)

    (loss, aux), grads = jax.value_and_grad(m.loss, has_aux=True)(params, batch)
    assert np.isfinite(float(loss)) and float(loss) > 0
    gnorm = jax.tree.reduce(
        lambda a, g: a + jnp.sum(g.astype(jnp.float32) ** 2), grads, 0.0) ** 0.5
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0

    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :S]
    logits, caches = m.prefill(params, pre)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    caches = m.init_caches(B, S + 8, filled=S)
    tok = jnp.ones((B, 1), jnp.int32)
    # [:2]: with a share of its experts held, a step also counts its rows
    dl, caches2 = m.decode_step(params, tok, caches,
                                jnp.full((B,), S, jnp.int32))[:2]
    assert dl.shape == (B, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(dl, np.float32)))
    # decode twice: cache must advance without shape drift
    dl2 = m.decode_step(params, tok, caches2,
                        jnp.full((B,), S + 1, jnp.int32))[0]
    assert dl2.shape == dl.shape


@pytest.mark.parametrize("arch", arch_ids())
def test_scanned_decode_matches_unscanned(arch):
    """A decode step over scanned layers, which writes the step's rows and
    states into its stacked caches in place, gives exactly what the
    unscanned layers give: tokens, logits and every cache leaf (the rows no
    step wrote, and Whisper's cross-attention K/V, included), after two
    donated steps from one prefill."""
    from repro.serve.engine import _install_prefix

    base = get_config(arch).reduced()
    # past a dense prefix of one layer, two MoE layers to scan
    base = dataclasses.replace(base,
                               n_layers=base.n_layers + base.n_dense_layers)
    models = {scan: build_model(dataclasses.replace(base, scan_layers=scan))
              for scan in (True, False)}
    assert models[False].inplace_layers == 0
    if base.layer_types:        # one unscanned period: nothing in place
        assert models[True].inplace_layers == 0
    else:
        assert models[True].inplace_layers >= 2
    params = models[False].init(jax.random.key(1))
    batch = _batch(base)
    batch["tokens"] = batch["tokens"][:, :S]
    logits, pre = jax.jit(models[False].prefill)(params, batch)
    filled = S + (base.num_patches if base.family == "vlm" else 0)
    max_seq = filled + 8

    out = {}
    for scan, m in models.items():
        caches = _install_prefix(m.init_caches(B, max_seq, filled=filled),
                                 jax.tree.map(jnp.copy, pre), max_seq)
        step = jax.jit(m.decode_step, donate_argnums=(2,))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        seen = []
        for i in range(2):
            lg, caches = step(params, tok, caches,
                              jnp.full((B,), filled + i, jnp.int32))[:2]
            tok = jnp.argmax(lg[:, -1], axis=-1)[:, None].astype(jnp.int32)
            seen.append((np.asarray(tok), np.asarray(lg)))
        out[scan] = seen, jax.tree.map(np.asarray, caches)

    (got, got_caches), (want, want_caches) = out[True], out[False]
    for (t1, l1), (t2, l2) in zip(got, want):
        np.testing.assert_array_equal(t1, t2)
        np.testing.assert_array_equal(l1, l2)
    assert (jax.tree.structure(got_caches)
            == jax.tree.structure(want_caches))
    for c1, c2 in zip(jax.tree.leaves(got_caches),
                      jax.tree.leaves(want_caches)):
        np.testing.assert_array_equal(c1, c2)
    if base.family == "audio":          # read, never written
        for name in ("cross_k", "cross_v"):
            np.testing.assert_array_equal(got_caches[0]["b0"][name],
                                          np.asarray(pre[0]["b0"][name]))


@pytest.mark.parametrize("arch", arch_ids())
def test_full_config_faithful(arch):
    """The full (not reduced) config matches the assignment table."""
    cfg = get_config(arch)
    table = {
        "qwen1_5_32b": (64, 5120, 40, 40, 27392, 152064),
        "internlm2_1_8b": (24, 2048, 16, 8, 8192, 92544),
        "qwen1_5_110b": (80, 8192, 64, 8, 49152, 152064),
        "glm4_9b": (40, 4096, 32, 2, 13696, 151552),
        "kimi_k2_1t_a32b": (61, 7168, 64, 8, None, 163840),
        "deepseek_v3_671b": (61, 7168, 128, 128, None, 129280),
        "whisper_base": (6, 512, 8, 8, 2048, 51865),
        "phi_3_vision_4_2b": (32, 3072, 32, 32, 8192, 32064),
        "recurrentgemma_2b": (26, 2560, 10, 1, 7680, 256000),
        "mamba2_130m": (24, 768, 0, 0, 0, 50280),
        "granite_4_0_h_small": (40, 4096, 32, 8, 0, 100352),
    }
    # expert width, experts per token, experts
    experts = {
        "kimi_k2_1t_a32b": (2048, 8, 384),
        "deepseek_v3_671b": (2048, 8, 256),
        "granite_4_0_h_small": (768, 10, 72),
    }
    L, d, h, kv, ff, v = table[arch]
    assert cfg.n_layers == L and cfg.d_model == d
    assert cfg.n_heads == h and cfg.n_kv_heads == kv
    if ff is not None:
        assert cfg.d_ff == ff
    assert cfg.vocab_size == v
    assert (arch in experts) == bool(cfg.n_experts)
    if cfg.n_experts:
        assert (cfg.moe_d_ff, cfg.top_k, cfg.n_experts) == experts[arch]


def test_param_counts_sane():
    """Total parameter counts are in the advertised ballpark."""
    expect = {
        "internlm2_1_8b": (1.5e9, 2.5e9),
        "qwen1_5_32b": (30e9, 36e9),
        "qwen1_5_110b": (100e9, 120e9),
        "glm4_9b": (8e9, 11e9),
        "deepseek_v3_671b": (6.4e11, 7.2e11),
        "kimi_k2_1t_a32b": (0.95e12, 1.15e12),
        "whisper_base": (5e7, 1.2e8),
        "mamba2_130m": (1.0e8, 1.9e8),
        "granite_4_0_h_small": (30e9, 34e9),
    }
    for arch, (lo, hi) in expect.items():
        n = count_params_from_specs(get_config(arch))
        assert lo <= n <= hi, (arch, n)


def test_moe_active_params():
    cfg = get_config("deepseek_v3_671b")
    act = count_params_from_specs(cfg, active_only=True)
    assert 3.0e10 <= act <= 4.5e10      # ~37B active


def test_shape_cells_skips():
    """long_500k runs only for sub-quadratic archs; every cell defined."""
    for arch in arch_ids():
        cfg = get_config(arch)
        cells = shape_cells(cfg)
        assert set(cells) == set(SHAPES)
        if cfg.family in ("ssm", "hybrid"):
            assert cells["long_500k"] is not None
        else:
            assert cells["long_500k"] is None


def test_moe_routing_mass_conservation():
    """Every kept token slot contributes its (renormalised) gate weight; the
    MoE output is a convex combination of expert outputs per token."""
    from repro.models.layers import _moe_local
    cfg = get_config("kimi_k2_1t_a32b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(1))
    moe_p = params["stacks"][1]["b0"]["moe"] if cfg.n_dense_layers else None
    assert moe_p is not None
    x = jnp.asarray(RNG.standard_normal((16, cfg.d_model)), jnp.float32)
    y, aux = _moe_local(x, moe_p["router"], moe_p["experts"], cfg, None)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) > 0.0            # load-balance loss is positive
