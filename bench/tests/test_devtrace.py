"""The reduction from trace to metrics, by hand: on a small built trace laid
out as a TPU trace is (per-device XLA modules and ops, and the benchmark's
host spans), and on a short trace recorded on the chip."""

from __future__ import annotations

import pathlib
from types import SimpleNamespace

import pytest

from bench import devtrace, harness, roofline

DEV = "/device:TPU:0"


def _built() -> devtrace.Trace:
    """A window of 100 ns: a prefill (10-30) and three decode steps
    (40-50, 55-65, 80-90) inside one generate span (5-95); an op outside
    any module at 70-72."""
    mods = [(10, 30, "jit_prefill(1)"), (40, 50, "jit_decode_step(2)"),
            (55, 65, "jit_decode_step(2)"), (80, 90, "jit_decode_step(2)")]
    ops = [(10, 20, "fusion.1"), (18, 30, "fusion.2"), (40, 50, "dot.3"),
           (55, 60, "dot.3"), (60, 65, "copy.4"), (70, 72, "argmax.5"),
           (80, 90, "dot.3")]
    spans = [(0, 100, "bench.traced"), (2, 98, "bench.wave"),
             (5, 95, "bench.generate")]
    return devtrace.Trace({DEV: mods}, {DEV: ops}, spans)


def test_union_and_busy_time():
    assert devtrace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4),
                                                               (5, 8)]
    busy = devtrace.Busy([(10, 20), (18, 30), (40, 50)])
    assert busy.within(0, 100) == 30
    assert busy.within(15, 45) == 20
    assert busy.within(31, 39) == 0
    assert busy.within(42, 44) == 2
    assert busy.within(20, 10) == 0


def test_busy_share_idle_and_module_runs():
    tr = _built()
    busy, window = devtrace.busy_share(tr)
    assert (busy, window) == pytest.approx(((20 + 10 + 10 + 2 + 10) / 1e9,
                                            100 / 1e9))
    assert devtrace.runs(tr, DEV, "jit_decode_step") == [(40, 50), (55, 65),
                                                          (80, 90)]
    assert devtrace.runs_per_span(tr, DEV, "jit_decode_step",
                                  "bench.generate") == [[(40, 50), (55, 65),
                                                         (80, 90)]]


def test_leaves_leave_out_ops_that_hold_others():
    loop = [(0, 10, "while"), (1, 4, "a"), (4, 9, "b"), (12, 13, "c")]
    assert devtrace.leaves(loop) == [(1, 4, "a"), (4, 9, "b"), (12, 13, "c")]


def test_breakdown_names_ops_by_module_and_gaps_by_host_span():
    bd = devtrace.breakdown(_built())
    ops = dict(bd["device_ops"])
    assert ops["jit_decode_step:dot.3"] == pytest.approx(25e-9)
    assert ops["argmax.5"] == pytest.approx(2e-9)
    # 0-10 and 72-80 follow no module, 30-40 the prefill, 50-55 and 65-70
    # a decode step; 90-100 is named by its middle, 95, which generate
    # (5-95) no longer holds but the wave does
    assert dict(bd["idle_gaps"]) == pytest.approx({
        "bench.generate after nothing": 18e-9,
        "bench.generate after jit_prefill": 10e-9,
        "bench.generate after jit_decode_step": 10e-9,
        "bench.wave after jit_decode_step": 10e-9})


def _recorded() -> devtrace.Trace:
    """The start of a ``mamba2-130m.chat`` round recorded on a TPU v5e:
    its eager prefill and its first four decode steps, cut from a traced
    run of the benchmark (``fixtures/mamba2_round.json``)."""
    path = pathlib.Path(__file__).with_name("fixtures") / "mamba2_round.json"
    return devtrace.Trace.from_json(path.read_text())


def _covered(ivs, lo, hi) -> int:
    """Time within [lo, hi) that some of ``ivs`` covers, by a plain sweep."""
    total, cur = 0, None
    for s, e in sorted(ivs):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur is None or s > cur[1]:
            total += cur[1] - cur[0] if cur else 0
            cur = [s, e]
        else:
            cur[1] = max(cur[1], e)
    return total + (cur[1] - cur[0] if cur else 0)


def test_a_recorded_chip_trace_reduces_as_by_hand():
    tr = _recorded()
    dev = tr.devices()[0]
    ops = [(s, e) for s, e, _ in tr.ops[dev]]
    lo, hi = tr.window()
    # read off the fixture: the window, the ops' union, each decode step
    assert (lo, hi) == (47492047, 654508039)
    assert _covered(ops, lo, hi) == 61295778
    assert devtrace.busy_share(tr) == pytest.approx((0.061295778,
                                                     0.607015992))
    steps = devtrace.runs(tr, dev, "jit_decode_step")
    assert [e - s for s, e in steps] == [10388083, 10389397, 10386158,
                                         10389419]
    gaps = [(b - a) - _covered(ops, a, b)
            for (_, a), (b, _) in zip(steps, steps[1:])]
    assert gaps == [89985106, 90705920, 91022171]
    r = {"trace": tr}
    read = lambda name: harness.load_metric(name).read(r)
    assert read("decode_step_ms.serve") == pytest.approx(41553057 / 4 / 1e6)
    assert read("decode_gap_ms.serve") == pytest.approx(sum(gaps) / 3 / 1e6)
    assert read("device_idle.serve") == pytest.approx(
        100 * (1 - 61295778 / 607015992))
    # the host's scalar pulls between decode steps hold the device idle
    # longest, and copying the recurrent state is the costliest op
    bd = devtrace.breakdown(tr)
    assert bd["idle_gaps"][0][0] == "bench.wave after jit_dynamic_slice"
    assert bd["device_ops"][0][0] == ("jit_decode_step:%copy.17 "
                                      "f32[24,64,24,64,128] copy")


def _reading(tr, rounds, arch, spec):
    rec = {"rounds": rounds, "t0": 0.0, "seconds": 1e9,
           "trace_rounds": len(rounds)}
    return {"trace": tr, "record": rec, "config": spec,
            "workload": {"engine": {"max_batch": 4}},
            "architecture": arch, "peak": roofline.peak("TPU v5 lite")}


def test_serving_readers_on_the_built_trace():
    tr = _built()
    req = lambda n: SimpleNamespace(out=[0] * n)
    rd = SimpleNamespace(plen=16, t_back=1.0,
                         requests=[req(4), req(2), req(4)])
    spec = dict(hidden_size=8, intermediate_size=16, num_attention_heads=2,
                num_key_value_heads=1, num_hidden_layers=1, vocab_size=10)
    r = _reading(tr, [rd], "dense_decoder", spec)
    read = lambda name: harness.load_metric(name).read(r)
    assert read("decode_step_ms.serve") == pytest.approx(10e-6)
    # gaps 50-55 (idle 5) and 65-80 (idle 13: argmax ran 70-72)
    assert read("decode_gap_ms.serve") == pytest.approx(9e-6)
    assert read("prefill_ms.serve") == pytest.approx(20e-6)
    assert read("device_idle.serve") == pytest.approx(48.0)
    # 3 + 1 + 3 decoded tokens in 3 steps of 4 slots
    assert read("slot_useful_share.serve") == pytest.approx(100 * 7 / 12)
    flops = sum(roofline.dense_decode_step(spec, 3, 16 + j + 1)[0]
                for j in range(3))
    assert read("decode_mfu.serve") == pytest.approx(
        100 * flops / (30e-9 * 197e12))
    rd.requests.append(req(6))        # five steps made, three traced
    assert read("decode_mfu.serve") is None
