"""The program's own host spans (``repro.spans``) on a profiler trace's
clock, and the device idle inside them, for the readers of the serving
engine's phases.

The program keeps its spans in memory, on ``time.perf_counter_ns``, while
the profiler traces; the trace holds the benchmark's ``bench.generate`` spans
on the profiler's clock.  :func:`traced_calls` takes the program's last k
``serve.generate`` spans for the trace's k ``bench.generate`` spans, and
gives ``None``, logging why, where the program keeps no spans or kept
fewer calls than that.

:func:`aligned` moves those calls' spans onto the device line in two steps.
First the host: it pairs the calls with the ``bench.generate`` spans in
order.  Each ``serve.generate`` runs inside its ``bench.generate``, and a
pause (a collection, the thread preempted) can only put its start later,
so the program's spans move by the largest start difference: the call
that started soonest after its ``bench.generate`` starts with it.  It gives
``None``, logging why, where a call then ends more than ``TOLERANCE_NS``
after its ``bench.generate``.  Then the device: in some traces the
device's events sit a constant offset off the host's, up to about a
millisecond.  Causal order bounds that offset: a decode step cannot start
on the device before the host opens the ``serve.dispatch`` span that
dispatches it, and the ``serve.emit`` span after it, which pulls its
tokens, cannot end before the step does.  Where the device line breaks
one of these orders, :func:`aligned` moves the spans by the least that
restores it, and logs that; where no move restores both, by more than
``TOLERANCE_NS``, it gives ``None``.  The order bounds the offset from one
side only, so after a move the split between emit and dispatch still errs
by up to the run's shortest launch of a step (section 7 of PERF.md); their
sum does not move.

The decode gaps are those of ``decode_gap_ms.serve``: between two
consecutive ``jit_decode_step`` executions within one ``bench.generate``.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass

from bench import devtrace, harness

TOLERANCE_NS = 100_000


@dataclass
class Aligned:
    offset_ns: int           # program clock + offset = the trace's host clock
    residual_ns: int         # how far a call ends after its bench.generate
    skew_ns: int             # the device line less the host line, by order
    calls: list              # per traced call: name -> [(start, end, args)]

    def spans(self, name: str) -> list:
        """Every call's spans of that name, in order, on the device line."""
        return [sp for call in self.calls for sp in call.get(name, [])]


def traced_calls(trace: devtrace.Trace):
    """The program's ``serve.generate`` spans of the traced calls and, for
    each, its children by name, on the program's clock: (calls, children),
    or ``None``."""
    try:
        from repro import spans
    except ImportError:
        harness.log("[progspans] the program keeps no spans (no repro.spans)")
        return None
    kept = spans.recorded()
    k = len(trace.spans_named("bench.generate"))
    calls = [s for s in kept if s.name == "serve.generate"]
    if not k or len(calls) < k:
        harness.log(f"[progspans] {len(calls)} serve.generate spans kept for "
                    f"{k} traced bench.generate spans")
        return None
    calls = calls[len(calls) - k:]
    index = {c.span_id: i for i, c in enumerate(calls)}
    children: list[dict] = [{} for _ in calls]
    for s in kept:
        i = index.get(s.parent_id)
        if i is not None:
            children[i].setdefault(s.name, []).append(s)
    return calls, children


def aligned(trace: devtrace.Trace) -> Aligned | None:
    """The program's spans of the traced ``generate`` calls (the calls' own
    spans and their children), moved onto the trace's device line."""
    got = traced_calls(trace)
    if got is None:
        return None
    calls, children = got
    traced = trace.spans_named("bench.generate")
    offset = max(a - c.start_ns for c, (a, _) in zip(calls, traced))
    residual = max(c.end_ns + offset - b for c, (_, b) in zip(calls, traced))
    if residual > TOLERANCE_NS:
        harness.log(f"[progspans] the program's serve.generate spans end up to "
                    f"{residual} ns after the trace's bench.generate, moved "
                    f"{offset} ns")
        return None
    moved = [{name: [(s.start_ns + offset, s.end_ns + offset, s.args)
                     for s in sps] for name, sps in kids.items()}
             for kids in children]
    for call, c in zip(moved, calls):
        call["serve.generate"] = [(c.start_ns + offset, c.end_ns + offset,
                                   c.args)]
    skew = device_skew(trace, moved)
    if skew is None:
        return None
    if skew:
        harness.log(f"[progspans] the device line breaks causal order; the "
                    f"spans move {skew} ns onto it")
        moved = [{name: [(s + skew, e + skew, a) for s, e, a in sps]
                  for name, sps in call.items()} for call in moved]
    return Aligned(offset, residual, skew, moved)


def device_skew(trace: devtrace.Trace, calls: list) -> int | None:
    """The least move of the host-aligned ``calls`` that puts every decode
    step after the start of its ``serve.dispatch`` and before the end of
    the ``serve.emit`` that follows it: 0 where the trace keeps that order,
    ``None`` where no move keeps both within ``TOLERANCE_NS``."""
    devs = trace.devices()
    if not devs:
        return 0
    late, early = [], []        # step start - dispatch start; step end - emit end
    for call, rs in zip(calls, devtrace.runs_per_span(
            trace, devs[0], "jit_decode_step", "bench.generate")):
        ds, es = call.get("serve.dispatch", []), call.get("serve.emit", [])
        if len(ds) != len(rs) or len(es) != len(rs) + 1:
            harness.log(f"[progspans] a traced call made {len(ds)} dispatches "
                        f"and {len(es)} emits around {len(rs)} traced decode "
                        f"steps")
            return None
        late += [s - d for (s, _), (d, _, _) in zip(rs, ds)]
        early += [e - f for (_, e), (_, f, _) in zip(rs, es[1:])]
    if not late:
        return 0
    hi, lo = min(late), max(early)
    if lo - hi > TOLERANCE_NS:
        harness.log(f"[progspans] no move of the spans keeps causal order on "
                    f"the device line: a step starts {-hi} ns before its "
                    f"dispatch and one ends {lo} ns after its emit")
        return None
    return hi if hi < 0 else max(lo, 0)


def decode_gaps(trace: devtrace.Trace) -> list[tuple[int, int]]:
    """(end of a decode step, start of the next) within each traced
    ``generate`` call, on the first device."""
    devs = trace.devices()
    if not devs:
        return []
    return [(a, b) for rs in devtrace.runs_per_span(
                trace, devs[0], "jit_decode_step", "bench.generate")
            for (_, a), (b, _) in zip(rs, rs[1:])]


def _parts(spans: list, gaps: list):
    """(start, end) of each part of the disjoint, sorted ``spans`` within
    each of the ``gaps``."""
    ends = [e for _, e, _ in spans]
    for lo, hi in gaps:
        i = bisect.bisect_right(ends, lo)
        while i < len(spans) and spans[i][0] < hi:
            yield max(spans[i][0], lo), min(spans[i][1], hi)
            i += 1


def idle_ms_per_gap(trace: devtrace.Trace, name: str) -> float | None:
    """Device idle inside the program's ``name`` spans within the decode
    gaps, in ms, per gap."""
    gaps = decode_gaps(trace)
    al = aligned(trace) if gaps else None
    if al is None:
        return None
    busy = trace.busy(trace.devices()[0])
    idle = sum((b - a) - busy.within(a, b)
               for a, b in _parts(al.spans(name), gaps))
    return idle / len(gaps) / 1e6


def syncs_per_step(trace: devtrace.Trace) -> float | None:
    """The ``syncs`` of the ``serve.emit`` spans between two of a traced
    call's ``serve.dispatch`` spans, per such gap.  In each call emits and
    dispatches alternate, an emit first and last, so those emits are its
    second to its last but one, and each dispatch launches one decode
    step; the program's spans alone give the count."""
    got = traced_calls(trace)
    if got is None:
        return None
    syncs = gaps = 0
    for kids in got[1]:
        emits = kids.get("serve.emit", [])
        steps = len(kids.get("serve.dispatch", []))
        if len(emits) != steps + 1:
            harness.log(f"[progspans] a traced call made {len(emits)} emits "
                        f"around {steps} dispatches")
            return None
        syncs += sum(s.args["syncs"] for s in emits[1:-1])
        gaps += max(steps - 1, 0)
    return syncs / gaps if gaps else None


def prefill_idle_ms(trace: devtrace.Trace) -> float | None:
    """Device idle inside each traced ``serve.prefill`` span, in ms, per
    span."""
    devs = trace.devices()
    al = aligned(trace) if devs else None
    spans = al.spans("serve.prefill") if al else []
    if not spans:
        return None
    busy = trace.busy(devs[0])
    idle = sum((e - s) - busy.within(s, e) for s, e, _ in spans)
    return idle / len(spans) / 1e6
