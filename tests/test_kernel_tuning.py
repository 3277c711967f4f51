"""KernelWorkload: the repo's own Pallas kernels as first-class tunables.

Fast tests cover the pure-data surface (fingerprints, nests, legality red
nodes, schedule extraction, store round-trips, spec resolution, serving
feedback).  The interpret-mode verification sweeps across non-divisible
blocks and causal/GQA variants are ``pallas``-marked (slow, deselected by
default — run with ``pytest -m pallas``), mirroring the ``pool`` marker.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (Configuration, PallasBackend, SearchSpace, Tile,
                        TuningSession, TuningSpec, attention_workload,
                        kernel_workload, serve_overrides, ssd_workload)
from repro.core.codegen import CodegenError
from repro.core.resultstore import ResultStore
from repro.core.transformations import (Interchange, Parallelize, Unroll)

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


# ---------------------------------------------------------------------------
# identity: fingerprints and structure keys
# ---------------------------------------------------------------------------


def test_fingerprint_stable_and_sensitive():
    a = attention_workload(seq_q=256, seq_kv=256)
    assert a.fingerprint() == attention_workload(
        seq_q=256, seq_kv=256).fingerprint()
    # every semantic knob must move the fingerprint (store-key safety)
    variants = [
        attention_workload(seq_q=128, seq_kv=256),
        attention_workload(seq_q=256, seq_kv=256, causal=False),
        attention_workload(seq_q=256, seq_kv=256, heads_q=16, heads_kv=2),
        attention_workload(seq_q=256, seq_kv=256, head_dim=128),
        ssd_workload(seq=256),
    ]
    fps = {a.fingerprint()} | {v.fingerprint() for v in variants}
    assert len(fps) == 1 + len(variants)


def test_nest_structure_and_reductions():
    a = attention_workload(seq_q=256, seq_kv=128, heads_q=4, heads_kv=2)
    n = a.nest()
    assert [(l.name, l.trips) for l in n.loops] == [
        ("h", 4), ("q", 256), ("kv", 128)]
    assert n.reduction_vars() == ("kv",)        # softmax/PV accumulation
    assert n.triangular == (("q", "kv"),)       # causal bound
    nc = attention_workload(seq_q=256, seq_kv=128, causal=False).nest()
    assert nc.triangular == ()

    s = ssd_workload(heads=4, seq=256).nest()
    assert [(l.name, l.trips) for l in s.loops] == [("h", 4), ("l", 256)]
    assert s.reduction_vars() == ("l",)         # the sequential state pass


def test_kernel_workload_factory_and_spec_resolution():
    w = kernel_workload("attention", seq_q=64, seq_kv=64)
    assert w.kernel == "attention" and w.extents["q"] == 64
    with pytest.raises(ValueError, match="unknown kernel workload"):
        kernel_workload("conv3d")
    with pytest.raises(ValueError, match="multiple of"):
        attention_workload(heads_q=7, heads_kv=2)

    spec = TuningSpec(workload="ssd", workload_args={"seq": 128, "heads": 4},
                      backend="pallas")
    assert spec.build_workload().extents == {"h": 4, "l": 128}
    # workload_args stay rejected for the paper workloads
    with pytest.raises(ValueError, match="only valid for"):
        TuningSpec(workload="gemm",
                   workload_args={"seq": 1}).build_workload()


# ---------------------------------------------------------------------------
# schedule extraction: tiles → block sizes, red nodes for the inexpressible
# ---------------------------------------------------------------------------


def test_kernel_params_untiled_and_tiled():
    a = attention_workload(seq_q=256, seq_kv=256)
    assert a.kernel_params(a.nest()) == {"block_q": 256, "block_kv": 256}
    cfg = Configuration().child(Tile(loops=("q", "kv"), sizes=(64, 32)))
    assert a.kernel_params(cfg.apply(a.nest())) == {
        "block_q": 64, "block_kv": 32}

    s = ssd_workload(seq=256)
    assert s.kernel_params(s.nest()) == {"chunk": 256}
    scfg = Configuration().child(Tile(loops=("l",), sizes=(64,)))
    assert s.kernel_params(scfg.apply(s.nest())) == {"chunk": 64}


def test_kernel_params_red_nodes():
    a = attention_workload(seq_q=256, seq_kv=256, heads_q=32, heads_kv=8)
    # tiling the head/grid dim: no kernel knob
    head_tiled = Configuration().child(
        Tile(loops=("h",), sizes=(8,))).apply(a.nest())
    with pytest.raises(CodegenError, match="not tileable"):
        a.kernel_params(head_tiled)
    # two stacked tiling levels on one var: single blocking level only
    twice = Configuration().child(
        Tile(loops=("q", "kv"), sizes=(64, 64))).child(
        Tile(loops=("q2", "kv2"), sizes=(16, 16))).apply(a.nest())
    with pytest.raises(CodegenError, match="single blocking level"):
        a.kernel_params(twice)
    # reordered grid: the pallas_call grid order is fixed
    swapped = Configuration().child(
        Interchange(loops=("h", "q", "kv"),
                    permutation=("q", "h", "kv"))).apply(a.nest())
    with pytest.raises(CodegenError, match="grid order"):
        a.kernel_params(swapped)
    # unroll: no such knob on these kernels
    unrolled = Configuration().child(Unroll(loop="kv", factor=4)).apply(
        a.nest())
    with pytest.raises(CodegenError, match="unroll"):
        a.kernel_params(unrolled)


def test_backend_red_nodes_match_paper_semantics():
    """Through the backend the red nodes surface with the paper's statuses:
    reduction-parallelization and triangular-bound violations are
    ``illegal``, inexpressible schedules ``compile_error``."""
    be = PallasBackend(verify=False)
    a = attention_workload(seq_q=256, seq_kv=256)
    r = be.evaluate(a, Configuration().child(Parallelize(loop="kv")))
    assert r.status == "illegal" and "reduction" in r.note
    # causal: kv tiled while q is untiled violates the triangular bound
    r = be.evaluate(a, Configuration().child(Tile(loops=("kv",), sizes=(64,))))
    assert r.status == "illegal" and "triangular" in r.note
    # ...but is perfectly legal on the non-causal variant
    nc = attention_workload(seq_q=256, seq_kv=256, causal=False)
    r = be.evaluate(nc, Configuration().child(Tile(loops=("kv",), sizes=(64,))))
    assert r.status == "ok"
    s = ssd_workload(seq=256)
    r = be.evaluate(s, Configuration().child(Parallelize(loop="l")))
    assert r.status == "illegal" and "reduction" in r.note
    r = be.evaluate(s, Configuration().child(Unroll(loop="l", factor=2)))
    assert r.status == "compile_error" and "unroll" in r.note


# ---------------------------------------------------------------------------
# store round-trip: fingerprint + structure key persistence
# ---------------------------------------------------------------------------


def test_store_roundtrip_keys_kernel_schedules(tmp_path):
    store_path = tmp_path / "kernels.jsonl"
    be = PallasBackend(verify=False)     # cost-model only: fast
    sess = TuningSession(be, store=str(store_path))
    a = attention_workload(seq_q=256, seq_kv=256, heads_q=4, heads_kv=2)
    space = SearchSpace(root=a.nest(), tile_sizes=(32, 64),
                        max_transformations=2)
    log = sess.tune(a, space, strategy="greedy", budget=30)
    best = log.best()

    loaded = ResultStore.open(str(store_path)).load(
        a.fingerprint(), be.store_scope())
    assert loaded, "no records persisted for the kernel fingerprint"
    # the root and the winning schedule both round-trip by structure key
    root_key = a.nest().structure_key()
    best_key = best.config.apply(a.nest()).structure_key()
    assert root_key in loaded
    assert best_key in loaded
    assert loaded[best_key].time_s == best.result.time_s

    # replay: a second cold session over the same space re-uses the store
    # and lands on the identical best without new measurement noise
    log2 = TuningSession(PallasBackend(verify=False),
                         store=str(store_path)).tune(
        a, SearchSpace(root=a.nest(), tile_sizes=(32, 64),
                       max_transformations=2),
        strategy="greedy", budget=30)
    assert log2.best().result.time_s == best.result.time_s


def test_session_cli_end_to_end_attention_spec(tmp_path):
    """Acceptance: a TuningSpec JSON with ``workload: attention`` runs end
    to end through ``python -m repro.core.session``."""
    spec = {
        "workload": "attention",
        "workload_args": {"seq_q": 128, "seq_kv": 128, "heads_q": 4,
                          "heads_kv": 2, "head_dim": 32},
        "backend": "pallas",
        "backend_args": {"verify": False},
        "space_args": {"tile_sizes": [32, 64], "max_transformations": 2},
        "strategy": "greedy",
        "budget": 25,
        "store": False,
    }
    spec_path = tmp_path / "attn_spec.json"
    spec_path.write_text(json.dumps(spec))
    out_path = tmp_path / "log.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("CC_RESULT_STORE", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.core.session", str(spec_path),
         "--out", str(out_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr}"
    assert "best time_s=" in proc.stdout
    payload = json.loads(out_path.read_text())
    statuses = {e["status"] for e in payload["experiments"]}
    assert "ok" in statuses


def test_serve_overrides_mapping(tmp_path):
    assert serve_overrides("attention", {"block_q": 256, "block_kv": 128}) \
        == {"attn_q_chunk": 256}
    assert serve_overrides("ssd", {"chunk": 64}) == {"ssd_chunk": 64}
    with pytest.raises(ValueError, match="no serving knob"):
        serve_overrides("conv3d", {})

    from repro.configs.base import get_config
    from repro.launch.serve import apply_tuned_schedules

    sched = tmp_path / "kernel_schedules.json"
    sched.write_text(json.dumps(
        {"attention": {"block_q": 64, "block_kv": 64}, "ssd": {"chunk": 32}}))
    cfg, overrides = apply_tuned_schedules(get_config("internlm2_1_8b"),
                                           sched)
    assert cfg.attn_q_chunk == 64 and cfg.ssd_chunk == 32
    assert overrides == {"attn_q_chunk": 64, "ssd_chunk": 32}


# ---------------------------------------------------------------------------
# interpret-mode correctness of tuned schedules vs the ref.py oracle
# (slow sweeps: pallas-marked, like the pool marker)
# ---------------------------------------------------------------------------


def _check_schedule(w, config, rtol=2e-4, atol=2e-4):
    nest = config.apply(w.nest())
    args = w.make_args()
    got = np.asarray(w.build(nest, interpret=True)(args))
    want = np.asarray(w.reference(args))
    err = float(np.abs(got - want).max())
    assert np.allclose(got, want, rtol=rtol, atol=atol), (
        f"{w.name} {w.kernel_params(nest)}: max err {err:.3e}")


@pytest.mark.pallas
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("heads_q,heads_kv", [(4, 4), (8, 2)])
def test_tuned_attention_schedules_vs_ref(causal, heads_q, heads_kv):
    """Tiled attention schedules (including blocks that do not divide the
    sequence — the pad/mask path) match the dense oracle across causal and
    GQA/MHA variants."""
    w = attention_workload(seq_q=96, seq_kv=96, heads_q=heads_q,
                           heads_kv=heads_kv, head_dim=32, causal=causal)
    _check_schedule(w, Configuration())                      # 96/96 blocks
    _check_schedule(w, Configuration().child(
        Tile(loops=("q", "kv"), sizes=(64, 64))))            # 96 % 64 != 0
    _check_schedule(w, Configuration().child(
        Tile(loops=("q", "kv"), sizes=(32, 32))))            # divisible
    _check_schedule(w, Configuration().child(
        Tile(loops=("q",), sizes=(40,))))                    # q-only, ragged


@pytest.mark.pallas
def test_tuned_attention_uneven_seq_lengths():
    # decode-like: fewer queries than keys, causal offset in play
    w = attention_workload(seq_q=48, seq_kv=112, heads_q=4, heads_kv=2,
                           head_dim=32, causal=True)
    _check_schedule(w, Configuration().child(
        Tile(loops=("q", "kv"), sizes=(32, 32))))
    _check_schedule(w, Configuration())


@pytest.mark.pallas
@pytest.mark.parametrize("seq,chunk", [(96, 64), (128, 32), (100, 48)])
def test_tuned_ssd_schedules_vs_ref(seq, chunk):
    """Tiled SSD chunk schedules (divisible and ragged) match the literal
    recurrence oracle."""
    w = ssd_workload(heads=4, seq=seq, proj=32, state=32)
    _check_schedule(w, Configuration().child(
        Tile(loops=("l",), sizes=(chunk,))), rtol=5e-4, atol=5e-4)


@pytest.mark.pool
def test_kernel_workload_through_supervised_pool():
    """KernelWorkload pickles over the SupervisedPool pipe and rebuilds in
    a spawn worker (the registry repopulates on module import)."""
    w = attention_workload(seq_q=64, seq_kv=64, heads_q=4, heads_kv=2,
                           head_dim=16)
    be = PallasBackend(scale=0.5, process_workers=1, timeout_s=120,
                       interpret=True)
    try:
        cfgs = [Configuration(),
                Configuration().child(Tile(loops=("q", "kv"),
                                           sizes=(32, 32)))]
        out = be.evaluate_many(w, cfgs)
    finally:
        be.close()
    assert [r.status for r in out] == ["ok", "ok"]
    assert out[1].time_s <= out[0].time_s


# ---------------------------------------------------------------------------
# verification modes: Mosaic on a TPU, the interpreter only when asked for
# ---------------------------------------------------------------------------


def test_store_scope_separates_interpret_and_mosaic():
    mosaic = PallasBackend().store_scope()
    interp = PallasBackend(interpret=True).store_scope()
    assert mosaic != interp
    assert "mosaic" in mosaic and "scale" not in mosaic
    assert "interpret" in interp and "scale=0.05" in interp
    # the interpreter's scale changes what it verifies; Mosaic runs at full
    # extents, so scale is not part of its scope
    assert (PallasBackend(interpret=True, scale=0.5).store_scope()
            != interp)
    assert PallasBackend(scale=0.5).store_scope() == mosaic


def test_mosaic_verification_off_tpu_raises_instead_of_red_nodes():
    """Off a TPU a Mosaic backend must fail loudly, never quietly turn every
    candidate red or fall back to the interpreter."""
    a = attention_workload(seq_q=64, seq_kv=64, heads_q=4, heads_kv=2,
                           head_dim=16)
    with pytest.raises(RuntimeError, match="needs a TPU.*interpret=True"):
        PallasBackend().evaluate(a, Configuration())
    assert PallasBackend(interpret=True).evaluate(
        a, Configuration()).status == "ok"


def test_mosaic_refusal_is_compile_error(monkeypatch):
    """A kernel Mosaic will not lower is a ``compile_error`` red node, not
    an ``exec_error``: here the CPU backend's refusal of a non-interpret
    Pallas call stands in for the chip compiler's."""
    from repro.core import measure

    monkeypatch.setattr(measure, "_on_tpu", lambda: True)
    a = attention_workload(seq_q=64, seq_kv=64, heads_q=4, heads_kv=2,
                           head_dim=16)
    r = PallasBackend().evaluate(a, Configuration())
    assert r.status == "compile_error" and "interpret" in r.note


@pytest.mark.parametrize("kwargs", [{}, {"interpret": True}])
def test_process_workers_refused_on_tpu_host(monkeypatch, kwargs):
    from repro.core import measure

    monkeypatch.setattr(measure, "_on_tpu", lambda: True)
    with pytest.raises(ValueError, match="one process"):
        PallasBackend(process_workers=1, **kwargs)
    PallasBackend(**kwargs)        # in-process measurement stays allowed
