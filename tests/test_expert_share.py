"""The expert layer for the experts one chip holds (``layers.routed_share``,
``layers.expert_ffn``): shares add up to the uncut layer, a skewed router
loses no token, and a model holding a share counts the rows it computes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import layers
from repro.models.model import build_model, layer_groups

RNG = np.random.default_rng(0)


def _cfg(**fields):
    """Granite's smoke configuration in float32, all 8 experts held."""
    cfg = get_config("granite_4_0_h_small").reduced()
    return dataclasses.replace(cfg, n_experts_held=0, **fields)


def _layer(cfg, seed=1):
    return layers.moe_params_init(jax.random.key(seed), cfg)


def _x(cfg, t=32):
    return jnp.asarray(RNG.standard_normal((t, cfg.d_model)), jnp.float32)


@pytest.mark.parametrize("sizes", [(3, 3, 2), (1,) * 8, (5, 3), (8,)])
def test_disjoint_shares_add_up_to_the_uncut_layer(sizes):
    cfg = _cfg()
    p, x = _layer(cfg), _x(cfg)
    whole, rows = layers.expert_ffn(x[None], p, cfg)
    total = layers.mlp(x[None], p["shared"], cfg)[0]      # counted once
    counted, first = 0, 0
    for n in sizes:
        share = jax.tree.map(lambda t: t[first:first + n], p["experts"])
        y, r = layers.routed_share(x, p["router"], share, cfg.top_k, first)
        total, counted, first = total + y, counted + int(r), first + n
    assert first == cfg.n_experts
    np.testing.assert_allclose(total, whole[0], rtol=1e-5, atol=1e-6)
    assert counted == int(rows) == x.shape[0] * cfg.top_k


def _one_by_one(x, p, cfg):
    """Each token through its own top-k experts, one at a time."""
    logits = np.asarray(x @ p["router"])
    out = []
    for t in range(x.shape[0]):
        top = np.argsort(-logits[t])[:cfg.top_k]
        w = np.exp(logits[t, top] - logits[t, top].max())
        w /= w.sum()
        y = 0
        for e, we in zip(top, w):
            g = jax.tree.map(lambda a: a[e], p["experts"])
            h = jax.nn.silu(x[t] @ g["gate"]) * (x[t] @ g["up"])
            y = y + we * (h @ g["down"])
        out.append(y)
    return np.stack(out)


def test_a_skewed_router_loses_no_token():
    """Every token picks expert 0: the held layer computes all of them,
    where the capacity path keeps ``capacity_factor`` · k·T/E a expert."""
    cfg = _cfg()
    p, x = _layer(cfg), _x(cfg)
    x = x.at[:, 0].set(10.0)
    p["router"] = p["router"].at[0, 0].set(100.0)
    assert bool(jnp.all(jnp.argmax(x @ p["router"], -1) == 0))
    want = _one_by_one(x, p, cfg)
    got, rows = layers.routed_share(x, p["router"], p["experts"], cfg.top_k)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert int(rows) == x.shape[0] * cfg.top_k
    capped, _ = layers._moe_local(x, p["router"], p["experts"], cfg, None)
    missed = np.abs(np.asarray(capped) - want).max(-1) > 1e-3
    assert missed.sum() >= x.shape[0] // 2        # 10 of 32 fit expert 0


def test_a_model_holding_a_share_counts_its_rows():
    cfg = get_config("granite_4_0_h_small").reduced()
    assert 0 < cfg.n_experts_held < cfg.n_experts
    m = build_model(cfg)
    assert m.expert_slots == cfg.n_layers * cfg.n_experts_held
    params = m.init(jax.random.key(0))
    B = 4
    caches = m.init_caches(B, 16, filled=3)
    tok = jnp.ones((B, 1), jnp.int32)
    logits, caches, rows = jax.jit(m.decode_step)(
        params, tok, caches, jnp.full((B,), 3, jnp.int32))
    assert rows.dtype == jnp.int32
    assert 0 < int(rows) <= B * cfg.top_k * cfg.n_layers
    whole = build_model(dataclasses.replace(cfg, n_experts_held=0))
    assert whole.expert_slots == 0
    out = whole.decode_step(whole.init(jax.random.key(0)), tok,
                            whole.init_caches(B, 16, filled=3),
                            jnp.full((B,), 3, jnp.int32))
    assert len(out) == 2


def test_the_layer_pattern_groups_into_its_period():
    cfg = get_config("granite_4_0_h_small")
    (period, reps), = layer_groups(cfg)
    assert reps == 4 and len(period) == 10
    assert period.index("attn_moe") == 5 and period.count("attn_moe") == 1
    assert layer_groups(dataclasses.replace(cfg, n_layers=10)) == [(period, 1)]
    with pytest.raises(ValueError, match="layer_types"):
        layer_groups(dataclasses.replace(cfg, n_layers=41))


def test_the_engine_records_each_steps_rows_beside_its_tokens():
    from repro import spans
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("granite_4_0_h_small").reduced()
    m = build_model(cfg)
    eng = ServeEngine(cfg, m.init(jax.random.key(0)), max_batch=2,
                      max_seq=32)
    spans.clear()
    with spans.recording():
        eng.generate([Request(prompt=[1, 2, 3, 4], max_new_tokens=5),
                      Request(prompt=[5, 6, 7, 8], max_new_tokens=3)])
    emits = [s.args for s in spans.recorded() if s.name == "serve.emit"]
    spans.clear()
    assert len(emits) == 5 and "expert_rows" not in emits[0]
    for a in emits[1:]:
        assert a["syncs"] == 1 and a["expert_slots"] == m.expert_slots
        assert 0 < a["expert_rows"] <= 2 * cfg.top_k * cfg.n_layers
