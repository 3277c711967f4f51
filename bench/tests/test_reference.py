"""The plain references against the program's prefill and cached decode at
a small size, with the program computing in float32; and the weights the
benchmark hands the program against the program's own layout."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.drivers import serve_closed
from bench.tests import small


@pytest.mark.parametrize("cell", small.CELLS)
def test_reference_matches_prefill_then_cached_decode(cell):
    from repro.models.model import build_model
    from repro.serve.engine import _install_prefix

    config = small.config(cell, dtype="float32")
    spec, ref = config["config"], harness.reference(config)
    cfg = harness.program_config(config)
    key = harness.seed_key(7)
    params = serve_closed.make_params(ref, spec, key, cfg)
    model = build_model(cfg)
    b, plen, steps, max_seq = 2, 16, 5, 32
    tokens = np.random.default_rng(0).integers(1, spec["vocab_size"],
                                               (b, plen + steps))
    logits, pre = model.prefill(params, {"tokens": jnp.asarray(
        tokens[:, :plen])})
    caches = _install_prefix(model.init_caches(b, max_seq, filled=plen), pre,
                             max_seq)
    got = [logits[:, -1]]
    decode = jax.jit(model.decode_step)
    for j in range(steps - 1):
        logits, caches = decode(params, jnp.asarray(tokens[:, plen + j,
                                                           None]),
                                caches, jnp.full((b,), plen + j, jnp.int32))
        got.append(logits[:, -1])
    got = np.stack([np.asarray(g) for g in got], axis=1)
    read = np.tile(np.arange(plen - 1, plen - 1 + steps), (b, 1))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(spec, key, jnp.asarray(tokens), read))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale, (
        np.abs(got - want).max(), scale)


@pytest.mark.parametrize("cell", small.CELLS)
def test_layer_weights_are_the_same_stacked_or_alone(cell):
    config = small.config(cell)
    spec, ref = config["config"], harness.reference(config)
    key = harness.seed_key(3)
    stacked = ref.program_params(spec, key, jnp.float32)
    from bench.reference import common

    one = ref.layer_weights(spec, common.layer_key(key, 1))
    flat = jax.tree.leaves(stacked["stacks"][0])
    assert sorted(float(np.asarray(t[1]).sum()) for t in flat) == sorted(
        float(np.asarray(t).sum()) for t in jax.tree.leaves(one))


def test_weights_follow_the_seed_beyond_32_bits():
    a, b = harness.seed_key(5), harness.seed_key(2**32 + 5)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(b))
    assert np.array_equal(jax.random.key_data(a),
                          jax.random.key_data(harness.seed_key(5)))


def test_layout_mismatch_is_refused():
    config = small.config("internlm2-1.8b.chat")
    cfg = harness.program_config(config)
    spec = dict(config["config"], intermediate_size=96)
    with pytest.raises(ValueError, match="layout"):
        serve_closed.make_params(harness.reference(config), spec,
                                 harness.seed_key(1), cfg)
