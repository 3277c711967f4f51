"""End-to-end behaviour tests: training learns, serving generates with a
correct KV cache, and the distributed MoE path agrees with the local path
(multi-device subprocess)."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_e2e_training_reduces_loss(tmp_path):
    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig
    from repro.optim import OptimizerConfig
    from repro.train.train_loop import LoopConfig, train

    cfg = get_config("internlm2_1_8b").reduced()
    opt = OptimizerConfig(lr=2e-3, total_steps=40, warmup_steps=5)
    loop = LoopConfig(total_steps=40, ckpt_every=100,
                      ckpt_dir=str(tmp_path / "ck"), log_every=5)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=4)
    res = train(cfg, opt, loop, data)
    first = res.losses[0][1]
    last = float(np.mean([l for _, l in res.losses[-2:]]))
    assert last < first - 0.5, res.losses


def test_serve_engine_generates():
    from repro.configs.base import get_config
    from repro.models.model import build_model
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("internlm2_1_8b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=128)
    reqs = [Request(prompt=[1, 2, 3, 4], max_new_tokens=8),
            Request(prompt=[9, 8, 7], max_new_tokens=8)]
    out = eng.generate(reqs)
    for r in out:
        assert r.done and len(r.out) == 8
        assert all(0 <= t < cfg.vocab_size for t in r.out)


def test_serve_decode_matches_prefill():
    """Greedy decode through the KV cache == rerunning prefill on the grown
    prompt (cache correctness end-to-end)."""
    from repro.configs.base import get_config
    from repro.models.model import build_model
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("glm4_9b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(1))
    prompt = [5, 11, 2, 7, 3]
    eng = ServeEngine(cfg, params, max_batch=1, max_seq=64)
    [r] = eng.generate([Request(prompt=list(prompt), max_new_tokens=4)])

    seq = list(prompt)
    want = []
    for _ in range(4):
        logits, _ = m.prefill(params, {"tokens": jnp.asarray([seq], jnp.int32)})
        nxt = int(jnp.argmax(logits[0, -1]))
        want.append(nxt)
        seq.append(nxt)
    assert r.out == want


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_config
    from repro.launch.mesh import smoke_mesh
    from repro.models import sharding as sh
    from repro.models.model import build_model

    cfg = get_config("kimi_k2_1t_a32b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 33)),
                                   jnp.int32)}
    loss_local, _ = m.loss(params, batch)          # no mesh: local MoE path

    mesh = smoke_mesh(2, 4)
    with sh.scope(mesh, dict(sh.DEFAULT_RULES)):
        loss_dist, _ = jax.jit(m.loss)(params, batch)  # shard_map EP path
    print(json.dumps({"local": float(loss_local), "dist": float(loss_dist)}))
""")


def test_moe_distributed_matches_local(tmp_path):
    """Expert-parallel shard_map MoE (all_to_all + FSDP gather) computes ≈ the
    same loss as the single-device path — subprocess with 8 forced host
    devices so this process keeps its 1-device view."""
    script = tmp_path / "multidev.py"
    script.write_text(MULTIDEV_SCRIPT)
    env = dict(os.environ,
               PYTHONPATH=str(pathlib.Path(__file__).resolve().parents[1] / "src"))
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # per-shard capacity changes which tokens drop → small tolerance
    assert abs(res["local"] - res["dist"]) < 0.05, res


SHARDED_TRAIN_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, jax
    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import smoke_mesh
    from repro.models import sharding as sh
    from repro.optim import OptimizerConfig
    from repro.train.train_loop import LoopConfig, train

    cfg = get_config("internlm2_1_8b").reduced()
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    opt = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=2)
    out = {}
    for name, mesh in (("one", None), ("mesh", smoke_mesh(2, 2))):
        loop = LoopConfig(ckpt_dir=os.path.join(sys.argv[1], name),
                          total_steps=2, log_every=1, ckpt_every=100)
        res = train(cfg, opt, loop, data, mesh=mesh,
                    rules=dict(sh.DEFAULT_RULES) if mesh else None)
        out[name] = {"losses": res.losses, "bytes": res.device_state_bytes}
    print(json.dumps(out))
""")


def test_sharded_training_creates_state_sharded(tmp_path):
    """On a (data 2, model 2) mesh the train loop creates params and
    optimizer state already sharded — each device holds about a quarter,
    not device 0 everything — and trains to the single-device losses."""
    script = tmp_path / "sharded_train.py"
    script.write_text(SHARDED_TRAIN_SCRIPT)
    env = dict(os.environ,
               PYTHONPATH=str(pathlib.Path(__file__).resolve().parents[1] / "src"))
    out = subprocess.run([sys.executable, str(script), str(tmp_path)],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    one, mesh = res["one"], res["mesh"]
    total = sum(one["bytes"])
    assert one["bytes"][0] == total              # unsharded: all on device 0
    # a quarter each, plus the few small leaves every device replicates
    assert all(0.25 <= b / total <= 0.3 for b in mesh["bytes"]), mesh["bytes"]
    for (s1, l1), (s2, l2) in zip(one["losses"], mesh["losses"]):
        assert s1 == s2 and abs(l1 - l2) < 1e-3 * abs(l1), res


def _greedy_per_slot(m, params, prompt, max_new, max_seq, eos_id=None):
    """One request alone: prefill, then decode steps, pulling each token
    and keeping the position as the engine's stop rules read it."""
    from repro.serve.engine import _install_prefix

    decode = jax.jit(m.decode_step)
    logits, pre = m.prefill(params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    caches = _install_prefix(
        m.init_caches(1, max_seq, filled=len(prompt)), pre, max_seq)
    pos, out = len(prompt), []
    while True:
        out.append(int(jnp.argmax(logits[0, -1])))
        if out[-1] == eos_id or len(out) >= max_new or pos + 1 >= max_seq:
            return out
        logits, caches = decode(params, jnp.asarray([[out[-1]]], jnp.int32),
                                caches, jnp.asarray([pos], jnp.int32))
        pos += 1


@pytest.fixture(scope="module")
def dense_model():
    from repro.configs.base import get_config
    from repro.models.model import build_model

    cfg = get_config("internlm2_1_8b").reduced()
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.key(3))


_PROMPTS = [[3, 17, 9, 40], [8, 1, 22, 5], [60, 2, 2, 11]]


@pytest.mark.parametrize("with_eos", [False, True])
def test_serve_engine_matches_per_slot_greedy(dense_model, with_eos):
    """Mixed max_new_tokens, with and without an EOS id: each request gets
    exactly the tokens of a plain greedy loop over that request alone."""
    from repro.serve.engine import Request, ServeEngine

    cfg, m, params = dense_model
    max_new, max_seq = [2, 5, 8], 64
    eos_id = None
    if with_eos:        # a token the longest request makes mid-way
        eos_id = _greedy_per_slot(m, params, _PROMPTS[2], 8, max_seq)[3]
    want = [_greedy_per_slot(m, params, p, n, max_seq, eos_id)
            for p, n in zip(_PROMPTS, max_new)]
    if with_eos:
        assert len(want[2]) <= 4 and want[2][-1] == eos_id
    eng = ServeEngine(cfg, params, max_batch=3, max_seq=max_seq, eos_id=eos_id)
    got = eng.generate([Request(prompt=list(p), max_new_tokens=n)
                        for p, n in zip(_PROMPTS, max_new)])
    assert [r.out for r in got] == want
    assert all(r.done for r in got)


def test_serve_emit_makes_one_transfer_a_step(dense_model):
    """Every serve.emit moves its step's tokens in one transfer, and the
    emits frame the dispatches: one more emit than dispatches."""
    from repro import spans
    from repro.serve.engine import Request, ServeEngine

    cfg, m, params = dense_model
    eng = ServeEngine(cfg, params, max_batch=3, max_seq=64)
    spans.clear()
    with spans.recording():
        eng.generate([Request(prompt=list(p), max_new_tokens=n)
                      for p, n in zip(_PROMPTS, [2, 5, 8])])
    kept = spans.recorded()
    emits = [s for s in kept if s.name == "serve.emit"]
    dispatches = [s for s in kept if s.name == "serve.dispatch"]
    assert [s.args["syncs"] for s in emits] == [1] * len(emits)
    assert len(dispatches) == 7 and len(emits) == len(dispatches) + 1
    order = [s.name for s in sorted(emits + dispatches,
                                    key=lambda s: s.start_ns)]
    assert order == ["serve.emit", "serve.dispatch"] * 7 + ["serve.emit"]


@pytest.mark.parametrize("plen", [60, 63])
def test_serve_engine_stops_at_max_seq(dense_model, plen):
    """The host-side position stops a round where the position pulled from
    the device did: after max_seq - plen tokens, the last at max_seq - 1."""
    from repro.serve.engine import Request, ServeEngine

    cfg, m, params = dense_model
    max_seq = 64
    prompts = [[(7 * i + j) % 97 + 1 for j in range(plen)] for i in range(2)]
    want = [_greedy_per_slot(m, params, p, 16, max_seq) for p in prompts]
    assert [len(w) for w in want] == [max_seq - plen] * 2
    eng = ServeEngine(cfg, params, max_batch=2, max_seq=max_seq)
    got = eng.generate([Request(prompt=p, max_new_tokens=16) for p in prompts])
    assert [r.out for r in got] == want
    assert all(r.done for r in got)
