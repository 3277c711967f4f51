"""Granite 4.0-H's cell at small sizes on the CPU: the reference against
the served path (prefill, then decode through the caches), each of the
model's scales and NoPE against the reference, a rehearsal of the cell,
and the reader of the held experts' count on a traced round."""

from __future__ import annotations

import dataclasses
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import devtrace, harness
from bench.drivers import serve_closed
from repro import spans

CELL = "granite-4.0-h-small.chat"
#: every width cut; 12 experts routed over, 5 held, top 3; the file's
#: pattern cut to its first 6 layers (attention at layer 5)
SIZES = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=48, shared_intermediate_size=80,
             vocab_size=256, num_hidden_layers=6, mamba_d_head=16,
             mamba_n_heads=8, mamba_d_state=16, mamba_chunk_size=16,
             router_experts=12, num_local_experts=5, num_experts_per_tok=3)
#: the f32 program against the f32 reference differs by summation order
#: and the chunked against the sequential scan: about 1e-6 of the largest
#: logit here.  The float8 control reads near 0.1 of it
TOL = 1e-4
#: the rehearsal's limit on the mean gap of served tokens' logits
LIMIT = 4e-5


def config(layer_types=None, **program) -> dict:
    cfg = harness.load_json("configs", "granite-4.0-h-small.json")
    cfg["config"].update(SIZES)
    if layer_types:
        cfg["config"]["layer_types"] = layer_types
    cfg["program"] = program
    return cfg


def served_logits(cfg, params, tokens, plen: int, max_seq: int = 32):
    """The engine's path: prefill over ``tokens[:, :plen]``, its caches
    installed, then the engine's jitted decode step over the rest; the
    logits of each step and the rows counted."""
    from repro.serve.engine import ServeEngine, _install_prefix

    eng = ServeEngine(cfg, params, max_batch=len(tokens), max_seq=max_seq)
    b = len(tokens)
    logits, pre = eng.model.prefill(
        params, {"tokens": jnp.asarray(tokens[:, :plen])})
    caches = _install_prefix(eng.model.init_caches(b, max_seq, filled=plen),
                             pre, max_seq)
    got, rows = [logits[:, -1]], []
    for j in range(tokens.shape[1] - plen - 1):
        logits, caches, n = eng._decode(
            params, jnp.asarray(tokens[:, plen + j, None]), caches,
            jnp.full((b,), plen + j, jnp.int32))
        got.append(logits[:, -1])
        rows.append(int(n))
    return np.stack([np.asarray(g, np.float32) for g in got], 1), rows


def reference_logits(config, tokens, plen: int, mode: str = "f32"):
    spec, ref = config["config"], harness.reference(config)
    steps = tokens.shape[1] - plen
    read = np.tile(np.arange(plen - 1, plen - 1 + steps), (len(tokens), 1))
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(spec, harness.seed_key(7),
                                     jnp.asarray(tokens), read, mode))


def _tokens(b=2, n=21):
    return np.random.default_rng(0).integers(1, SIZES["vocab_size"], (b, n))


def _program(config, **fields):
    cfg = dataclasses.replace(harness.program_config(config), **fields)
    params = serve_closed.make_params(harness.reference(config),
                                      config["config"], harness.seed_key(7),
                                      cfg)
    return cfg, params


@pytest.mark.parametrize("layer_types", [
    None, ["mamba", "attention"] * 3])
def test_served_logits_agree_with_the_reference(layer_types):
    c = config(layer_types, dtype="float32", param_dtype="float32")
    cfg, params = _program(c)
    tokens = _tokens()
    got, rows = served_logits(cfg, params, tokens, 16)
    want = reference_logits(c, tokens, 16)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= TOL * scale, (
        np.abs(got - want).max(), scale)
    ctl = reference_logits(c, tokens, 16, "fp8")
    assert np.abs(ctl - want).max() > 30 * TOL * scale
    # each step, each layer: 2 tokens × 3 picks, some of them held here
    assert all(0 < n <= 2 * 3 * 6 for n in rows)


@pytest.mark.parametrize("field,default,spec", [
    ("embedding_multiplier", 1.0, {}), ("residual_multiplier", 1.0, {}),
    ("attention_multiplier", 0.0, {}), ("logits_scaling", 1.0, {}),
    # at 1/128 a head of 16 attends nearly evenly, and positions hardly
    # move it: NoPE is checked where attention is sharp
    ("position_embedding", "rope", {"attention_multiplier": 1.0})])
def test_each_scale_and_nope_match_the_reference(field, default, spec):
    """With the file's values the program agrees with the reference; with
    any one of them left at the default it does not."""
    c = config(dtype="float32", param_dtype="float32")
    c["config"].update(spec)
    tokens = _tokens()
    want = reference_logits(c, tokens, 16)
    scale = np.abs(want).max()
    cfg, params = _program(c)
    assert getattr(cfg, field) != default
    got, _ = served_logits(cfg, params, tokens, 16)
    assert np.abs(got - want).max() <= TOL * scale
    wrong, _ = served_logits(dataclasses.replace(cfg, **{field: default}),
                             params, tokens, 16)
    assert np.abs(wrong - want).max() > 30 * TOL * scale


def _context(seed: int = 2**33 + 5) -> harness.Context:
    wl = harness.load_json("workloads", CELL + ".json")
    wl["traffic"].update(
        clients=4, prompt_len={"values": [16, 32], "weights": [0.5, 0.5]},
        output_len={"values": [3, 6], "weights": [0.5, 0.5]})
    wl["engine"] = {"max_batch": 4, "max_seq": 40}
    # the cell's limits do not carry to these widths, where the logits
    # are a 256-token head's over 16: the program reads a mean gap of 0
    # to 2.2e-5 here over four seeds, the float8 control 6.1e-5 to 1.3e-4
    wl["check"] = {"tokens": 12,
                   "limits": {"served_token_gap_mean": LIMIT}}
    return harness.Context({"name": CELL}, wl, config(), seed, 0.5, False,
                           time.perf_counter())


def test_a_rehearsal_serves_every_request_correctly():
    out = serve_closed.run(_context())
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted >= 4


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The first decode step of every round hands back negated logits."""
    from repro.serve.engine import ServeEngine

    generate = ServeEngine.generate

    def broken(self, requests):
        decode, calls = self._decode, []

        def altered(*args):
            logits, *rest = decode(*args)
            calls.append(1)
            return (-logits if len(calls) == 1 else logits, *rest)

        self._decode = altered
        try:
            return generate(self, requests)
        finally:
            self._decode = decode

    monkeypatch.setattr(ServeEngine, "generate", broken)
    out = serve_closed.run(_context())
    assert not out.correct
    assert out.checks["served_token_gap_mean"]["value"] > LIMIT


def test_the_float8_control_reads_far_above_the_program():
    ctx = _context()
    server = serve_closed.setup(ctx)
    rounds = [serve_closed.one_round(server) for _ in range(3)]
    picked = serve_closed.sample(rounds, ctx.seed, 40)
    args = (server.ref, server.spec, server.key, picked)
    program = serve_closed.served_gaps(*args)
    control = serve_closed.served_gaps(*args, "fp8")
    assert program["served_token_gap_mean"] < LIMIT
    assert control["served_token_gap_mean"] > LIMIT
    assert control["served_token_gap_mean"] > 3 * program[
        "served_token_gap_mean"]


@pytest.fixture
def _empty():
    spans.clear()
    yield
    spans.clear()


def test_the_held_experts_count_reads_from_a_traced_round(_empty):
    server = serve_closed.setup(_context())
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            with jax.profiler.TraceAnnotation("bench.traced"):
                for _ in range(2):
                    serve_closed.one_round(server)
        finally:
            jax.profiler.stop_trace()
        tr = devtrace.load(tmp)
    emits = [s for s in spans.recorded() if s.name == "serve.emit"]
    counted = [s.args for s in emits if "expert_rows" in s.args]
    # one emit a round follows the prefill and carries no count
    assert len(counted) == len(emits) - 2
    assert {a["expert_slots"] for a in counted} == {6 * 5}
    assert {a["syncs"] for a in counted} == {1}
    # 4 slots × 3 picks a layer: 4 × 3 × 5 / 12 = 5 rows a layer held,
    # 1.25 tokens an expert, on average
    read = harness.load_metric("expert_tokens_per_step.serve").read(
        {"trace": tr})
    assert 0 < read <= 4 * 3 / 5
    assert harness.load_metric("host_syncs_per_step.serve").read(
        {"trace": tr}) == 1.0


def test_the_count_reads_nothing_where_the_program_keeps_none(_empty):
    tr = devtrace.Trace(spans=[(0, 10, "bench.generate")])
    with spans.recording():
        with spans.span("serve.generate"):
            for name in ("serve.emit", "serve.dispatch", "serve.emit",
                         "serve.dispatch", "serve.emit"):
                with spans.span(name, syncs=1):
                    pass
    assert harness.load_metric("expert_tokens_per_step.serve").read(
        {"trace": tr}) is None
