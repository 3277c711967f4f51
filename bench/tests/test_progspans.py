"""The engine's spans read back: on the CPU, a small cell under the profiler
(the program's ``serve.generate`` spans align with the trace's
``bench.generate``, the children tile each call, the pulls counted match
the tokens served); and the four readers on a built trace, by hand."""

from __future__ import annotations

import sys
import tempfile

import jax
import pytest

from bench import devtrace, harness, progspans
from bench.drivers import serve_closed
from bench.tests import small
from repro import spans

DEV = "/device:TPU:0"
U = 1000                    # ns: the built trace counts in microseconds
D = 7_000_000_000           # the program's clock less the trace's


@pytest.fixture(autouse=True)
def _empty():
    spans.clear()
    yield
    spans.clear()


def _traced_rounds(cell: str, n: int):
    """``n`` rounds of a small cell under the profiler, as ``serve_closed``
    traces them: (rounds, trace)."""
    server = serve_closed.setup(small.context(cell))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            with jax.profiler.TraceAnnotation("bench.traced"):
                rounds = [serve_closed.one_round(server) for _ in range(n)]
        finally:
            jax.profiler.stop_trace()
        return rounds, devtrace.load(tmp)


@pytest.mark.parametrize("cell", small.CELLS)
def test_engine_spans_align_tile_and_count_pulls_on_the_cpu(cell):
    rounds, tr = _traced_rounds(cell, 2)
    al = progspans.aligned(tr)
    assert al is not None and al.residual_ns <= progspans.TOLERANCE_NS
    assert len(al.spans("serve.generate")) == 2
    assert len(al.spans("serve.prefill")) == 2
    kept = spans.recorded()
    calls = [s for s in kept if s.name == "serve.generate"]
    assert len(calls) == 2                    # warm-up ran untraced
    between = gaps = 0
    for call, rd in zip(calls, rounds):
        kids = [s for s in kept if s.parent_id == call.span_id]
        covered = sum(s.end_ns - s.start_ns for s in kids)
        assert covered >= 0.95 * (call.end_ns - call.start_ns)
        emits = [s.args["syncs"] for s in kids if s.name == "serve.emit"]
        outs = [len(r.out) for r in rd.requests]
        # each iteration pulls its live slots' tokens, and the position
        # unless none is left live
        assert emits == [sum(n > j for n in outs) + (j < len(emits) - 1)
                         for j in range(len(emits))]
        assert len(emits) == max(outs)
        assert sum(emits) == sum(outs) + len(emits) - 1
        assert [s.name for s in kids].count("serve.dispatch") == max(outs) - 1
        between += sum(emits[1:-1])
        gaps += len(emits) - 2
    # the CPU trace holds no device: the readers of device idle read
    # nothing, and the pulls between two steps come from the spans alone
    r = {"trace": tr}
    for name in ("emit_idle_ms.serve", "dispatch_idle_ms.serve",
                 "prefill_idle_ms.serve"):
        assert harness.load_metric(name).read(r) is None
    assert harness.load_metric("host_syncs_per_step.serve").read(r) == \
        pytest.approx(between / gaps)


# ------------------------------------------------------------ built trace --


def _built(shifts=(0, 0), lag: int = 0) -> devtrace.Trace:
    """Two traced rounds.  The first (10-400 us) has a prefill at 50-150
    and decode steps at 200-240, 260-300 and 330-370, an argmax at 240-245
    and a pull at 310-312; the second (500-800) a prefill at 520-600 and
    decode steps at 650-690 and 700-740.  ``shifts`` move each round's
    device events later by that many us, as a skew between the device's
    and the host's clocks in the trace would; ``lag`` starts the first
    ``bench.generate`` that many us sooner, as a pause before the engine's
    call starts would."""
    steps = [(200, 240), (260, 300), (330, 370), (650, 690), (700, 740)]
    evs = ([(50, 150, "jit_prefill(1)", "fusion.1"),
            (520, 600, "jit_prefill(1)", "fusion.1"),
            (240, 245, None, "argmax.2"), (310, 312, None, "slice.3")]
           + [(s, e, "jit_decode_step(2)", "dot.4") for s, e in steps])
    mods, ops = [], []
    for s, e, mod, op in evs:
        d = shifts[s > 450]
        mods += [((s + d) * U, (e + d) * U, mod)] if mod else []
        ops.append(((s + d) * U, (e + d) * U, op))
    marks = [(0, 1000 * U, "bench.traced"),
             ((10 - lag) * U, 400 * U, "bench.generate"),
             (500 * U, 800 * U, "bench.generate")]
    return devtrace.Trace({DEV: sorted(mods)}, {DEV: sorted(ops)}, marks)


def _kept(second_end: int = 798, second_skew: int = 0) -> list:
    """The program's spans of an earlier untraced call and the two traced
    ones, on its own clock (the trace's, in us, plus ``D``); each call ends
    2 us before the trace's ``bench.generate``.  The second call's first
    decode step starts as its dispatch does, so causal order bounds a
    device line that sits earlier exactly.  ``second_skew`` moves the
    second call's prefill, emits and dispatches later."""
    out, ids = [], iter(range(1, 100))

    def call(lo, hi, kids, skew=0):
        gid = next(ids)
        for name, s, e, args in kids:
            out.append(spans.Span((s + skew) * U + D, (e + skew) * U + D,
                                  name, next(ids), gid, args))
        out.append(spans.Span(lo * U + D, hi * U + D, "serve.generate", gid,
                              None, {}))

    call(-300, -100, [("serve.prefill", -299, -200, {}),
                      ("serve.emit", -200, -150, {"syncs": 99})])
    call(10, 398, [("serve.prefill", 12, 180, {}),
                   ("serve.emit", 180, 190, {"syncs": 4}),
                   ("serve.dispatch", 190, 195, {}),
                   ("serve.emit", 195, 255, {"syncs": 4}),
                   ("serve.dispatch", 255, 265, {}),
                   ("serve.emit", 265, 320, {"syncs": 3}),
                   ("serve.dispatch", 320, 328, {}),
                   ("serve.emit", 329, 375, {"syncs": 2})])
    call(500, second_end, [("serve.prefill", 502, 640, {}),
                           ("serve.emit", 640, 650, {"syncs": 3}),
                           ("serve.dispatch", 650, 653, {}),
                           ("serve.emit", 653, 695, {"syncs": 3}),
                           ("serve.dispatch", 695, 699, {}),
                           ("serve.emit", 699, 760, {"syncs": 2})],
         second_skew)
    return out


READERS = ("emit_idle_ms.serve", "dispatch_idle_ms.serve",
           "host_syncs_per_step.serve", "prefill_idle_ms.serve")


def _read(monkeypatch, kept, shifts=(0, 0), lag=0):
    monkeypatch.setattr(spans, "recorded", lambda: list(kept))
    r = {"trace": _built(shifts, lag)}
    return {n: harness.load_metric(n).read(r)
            for n in READERS + ("decode_gap_ms.serve",)}


def test_engine_readers_on_the_built_trace(monkeypatch):
    monkeypatch.setattr(spans, "recorded", lambda: _kept())
    al = progspans.aligned(_built())
    assert (al.offset_ns, al.residual_ns, al.skew_ns) == (-D, -2 * U, 0)
    assert len(al.spans("serve.generate")) == 2          # the earlier left out
    got = _read(monkeypatch, _kept())
    # gaps 240-260 (argmax 240-245), 300-330 (pull 310-312), 690-700
    assert got["decode_gap_ms.serve"] == pytest.approx((15 + 28 + 10) / 3e3)
    # emit in them: 240-255 less the argmax; 300-320 less the pull, and
    # 329-330, where the next step's dispatch is done but the step has not
    # started; 690-695 and 699-700
    assert got["emit_idle_ms.serve"] == pytest.approx((10 + 19 + 6) / 3e3)
    # dispatch in them: 255-260, 320-328, 695-699; 328-329 is in neither
    assert got["dispatch_idle_ms.serve"] == pytest.approx((5 + 8 + 4) / 3e3)
    # the emits between two dispatches: 195-255 (4 pulls), 265-320 (3),
    # 653-695 (3)
    assert got["host_syncs_per_step.serve"] == pytest.approx(10 / 3)
    # 12-180 less the prefill's 100, 502-640 less its 80
    assert got["prefill_idle_ms.serve"] == pytest.approx((68 + 58) / 2e3)


@pytest.mark.parametrize("case", ["misaligned", "too_few_calls", "no_spans"])
def test_engine_readers_read_nothing_when_spans_do_not_fit(monkeypatch,
                                                           case):
    kept = _kept(second_end=950) if case == "misaligned" else _kept()
    if case == "too_few_calls":
        kept = [s for s in kept if s.start_ns >= 500 * U + D]
    if case == "no_spans":                  # a program without a recorder
        import repro

        monkeypatch.setitem(sys.modules, "repro.spans", None)
        monkeypatch.delattr(repro, "spans")
    got = _read(monkeypatch, kept)
    assert got["decode_gap_ms.serve"] is not None
    # the syncs need no clock: a misaligned call still counts its pulls
    counts = got.pop("host_syncs_per_step.serve")
    assert counts == (pytest.approx(10 / 3) if case == "misaligned" else None)
    assert all(got[n] is None for n in READERS if n in got), got


def test_a_pause_before_a_call_starts_keeps_the_alignment(monkeypatch):
    # the first call starts 150 us after its bench.generate, as after a
    # collection: the second, which starts with its own, sets the move,
    # and every reading is as without the pause
    monkeypatch.setattr(spans, "recorded", lambda: _kept())
    al = progspans.aligned(_built(lag=150))
    assert (al.offset_ns, al.residual_ns) == (-D, -2 * U)
    assert _read(monkeypatch, _kept(), lag=150) == \
        pytest.approx(_read(monkeypatch, _kept()))


def test_syncs_are_picked_by_order_not_by_time(monkeypatch):
    # the second call's spans moved 6 us later, or the device line 300 us
    # earlier: the emits between two dispatches are the same
    want = pytest.approx(10 / 3)
    assert _read(monkeypatch, _kept(second_skew=6))[
        "host_syncs_per_step.serve"] == want
    assert _read(monkeypatch, _kept(), (-300, -300))[
        "host_syncs_per_step.serve"] == want
    # an emit missing from a traced call: its pulls cannot be placed
    kept = [s for s in _kept() if s.args != {"syncs": 2}]
    got = _read(monkeypatch, kept)
    assert got["host_syncs_per_step.serve"] is None
    assert got["emit_idle_ms.serve"] is None      # nor the steps matched
    assert got["decode_gap_ms.serve"] is not None


@pytest.mark.parametrize("shift, bound", [(-30, 0), (20, 5)])
def test_a_skewed_device_line_moves_to_its_causal_bound(monkeypatch, shift,
                                                        bound):
    # 30 us early, a step starts 30 us before its dispatch (620 against
    # 650): the spans move 30 us earlier, which restores the trace as
    # built.  20 us late, a step ends 15 us after the emit that pulls its
    # tokens (690 + 20 against 695): they move 15 us later, to where a
    # line 5 us late already keeps both orders; the order bounds a late
    # line only that far.
    monkeypatch.setattr(spans, "recorded", lambda: _kept())
    al = progspans.aligned(_built((shift, shift)))
    assert al.skew_ns == (shift - bound) * U
    assert al.residual_ns == -2 * U                   # the host is unmoved
    got = _read(monkeypatch, _kept(), (shift, shift))
    want = _read(monkeypatch, _kept(), (bound, bound))
    assert got == pytest.approx(want)
    assert progspans.aligned(_built((bound, bound))).skew_ns == 0
    # early, the dispatches keep their 17 us of idle as built; unmoved,
    # every gap would end before its dispatch starts, and they would read 0
    if shift < 0:
        assert got["dispatch_idle_ms.serve"] == pytest.approx(17 / 3e3)


def test_a_device_line_no_move_can_order_reads_nothing(monkeypatch):
    # the first round's device events 100 us early, the second's 60 us
    # late: a step starts 95 us before its dispatch and another ends 55 us
    # after its emit; no one move keeps both within 100 us
    got = _read(monkeypatch, _kept(), (-100, 60))
    assert got["decode_gap_ms.serve"] is not None
    assert got["host_syncs_per_step.serve"] == pytest.approx(10 / 3)
    assert all(got[n] is None for n in READERS
               if n != "host_syncs_per_step.serve"), got
