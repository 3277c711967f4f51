"""Reduction of a profiler trace to intervals, and the arithmetic on them
that the per-layer metrics share.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
:class:`Trace`: for each device, its XLA modules and its XLA ops as
(start, end, name) in nanoseconds, and the benchmark's own host spans (the
``TraceAnnotation`` names that start with ``bench.``).  Host and device
events share the profiler's clock.  A :class:`Trace` also round-trips
through JSON, which is how the committed test fixture is kept.
"""

from __future__ import annotations

import bisect
import json
import pathlib
import re
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
_ID = re.compile(r"\(.*\)$")
_DEVICE = re.compile(r"/device:TPU:\d+$")


@dataclass
class Trace:
    modules: dict = field(default_factory=dict)   # device -> [(s, e, name)]
    ops: dict = field(default_factory=dict)       # device -> [(s, e, name)]
    spans: list = field(default_factory=list)     # [(s, e, name)] host

    def devices(self) -> list:
        return sorted(self.ops or self.modules)

    def window(self, name: str = SPAN_PREFIX + "traced") -> tuple[int, int]:
        """The traced window: the benchmark's span of that name."""
        for s, e, n in self.spans:
            if n == name:
                return s, e
        raise ValueError(f"no {name!r} span in the trace")

    def busy(self, device: str) -> "Busy":
        """The device's busy intervals: its ops, or its modules where the
        trace has no ops."""
        cache = self.__dict__.setdefault("_busy", {})
        if device not in cache:
            cache[device] = Busy(self.ops.get(device)
                                 or self.modules.get(device, []))
        return cache[device]

    def spans_named(self, name: str) -> list:
        return sorted((s, e) for s, e, n in self.spans if n == name)

    def to_json(self) -> str:
        return json.dumps({"modules": self.modules, "ops": self.ops,
                           "spans": self.spans})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        tup = lambda evs: [tuple(e) for e in evs]
        return cls(modules={k: tup(v) for k, v in d["modules"].items()},
                   ops={k: tup(v) for k, v in d["ops"].items()},
                   spans=tup(d["spans"]))


def load(logdir: str | pathlib.Path) -> Trace:
    """The newest ``.xplane.pb`` under ``logdir``, reduced."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(str(files[-1]))
    tr = Trace()
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    getattr(tr, key).setdefault(plane.name, []).extend(
                        (int(ev.start_ns), int(ev.end_ns), ev.name)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend((int(ev.start_ns), int(ev.end_ns), ev.name)
                                for ev in line.events
                                if ev.name.startswith(SPAN_PREFIX))
    for d in (tr.modules, tr.ops):
        for evs in d.values():
            evs.sort()
    tr.spans.sort()
    return tr


def module_name(name: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return _ID.sub("", name)


_OPCODE = re.compile(r"(?:^|[\s)])([a-z][\w\-]*)\(")


def op_label(name: str) -> str:
    """An XLA op event's name, the whole HLO instruction, cut to its name,
    result shape and opcode: ``%convert.21 bf16[24,8192,2048] convert``."""
    left, eq, rest = name.partition(" = ")
    if not eq:
        return name[:120]
    op = _OPCODE.search(rest)
    shape = "" if rest.startswith("(") else rest.split(" ")[0].split("{")[0]
    return " ".join(x for x in (left, shape, op[1] if op else "") if x)


# -------------------------------------------------------------- intervals --


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end, ...) intervals into disjoint (start, end)."""
    out: list[list[int]] = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Busy:
    """The union of some intervals, for fast sums over windows."""

    def __init__(self, intervals):
        self.ivs = union(intervals)
        self.starts = [s for s, _ in self.ivs]
        self.cum = [0]
        for s, e in self.ivs:
            self.cum.append(self.cum[-1] + e - s)

    def within(self, lo: int, hi: int) -> int:
        """Time within [lo, hi) that the union covers."""
        if hi <= lo:
            return 0
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        j = bisect.bisect_left(self.starts, hi)
        if i >= j:
            return 0
        total = self.cum[j] - self.cum[i]
        s, e = self.ivs[i]
        total -= max(0, min(e, lo) - s)           # the first's part before lo
        s, e = self.ivs[j - 1]
        total -= max(0, e - max(s, hi))           # the last's part after hi
        return total


def runs(trace: Trace, device: str, module: str) -> list[tuple[int, int]]:
    """(start, end) of each execution of ``module`` on ``device``."""
    return [(s, e) for s, e, n in trace.modules.get(device, [])
            if module_name(n) == module]


def runs_per_span(trace: Trace, device: str, module: str,
                  span: str) -> list[list[tuple[int, int]]]:
    """The executions of ``module`` that start inside each host span named
    ``span``, one list per span."""
    rs = runs(trace, device, module)
    return [[(s, e) for s, e in rs if a <= s < b]
            for a, b in trace.spans_named(span)]


def busy_share(trace: Trace) -> tuple[float, float]:
    """(busy seconds averaged over the devices, window seconds)."""
    lo, hi = trace.window()
    devs = trace.devices()
    busy = [trace.busy(d).within(lo, hi) for d in devs]
    return sum(busy) / len(devs) / 1e9, (hi - lo) / 1e9


# -------------------------------------------------------------- breakdown --


def leaves(evs) -> list:
    """The sorted events that hold no other: a loop's op (``while``) holds
    the ops of its body, which are listed too."""
    evs = sorted(evs, key=lambda ev: (ev[0], -ev[1]))
    return [ev for ev, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or not (nxt[0] < ev[1] and nxt[1] <= ev[1])]


def _enclosing(t: int, evs) -> str | None:
    """Name of the innermost of the (nested) events ``evs`` that holds
    time ``t``."""
    best = None
    for s, e, n in evs:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else None


def _at(mods: list, starts: list, t: int) -> str | None:
    """Name of the module of the sorted, disjoint ``mods`` running at
    ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    return mods[i][2] if i >= 0 and t < mods[i][1] else None


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took the most time and the idle gaps by
    what the host was doing, on the first device, each summed by name."""
    dev = trace.devices()[0]
    lo, hi = trace.window()
    mods = trace.modules.get(dev, [])
    starts = [s for s, _, _ in mods]
    ops: dict[str, int] = {}
    for s, e, n in leaves(trace.ops.get(dev, [])):
        if lo <= s < hi:
            mod = _at(mods, starts, s)
            key = f"{module_name(mod)}:{op_label(n)}" if mod else op_label(n)
            ops[key] = ops.get(key, 0) + e - s
    gaps: dict[str, int] = {}
    busy = [iv for iv in trace.busy(dev).ivs if iv[1] > lo and iv[0] < hi]
    edges = [(lo, lo)] + busy + [(hi, hi)]
    for (_, prev_end), (nxt, _) in zip(edges, edges[1:]):
        if nxt <= prev_end:
            continue
        mid = (max(prev_end, lo) + min(nxt, hi)) // 2
        span = _enclosing(mid, trace.spans) or "outside spans"
        mod = _at(mods, starts, prev_end - 1)
        key = f"{span} after {module_name(mod) if mod else 'nothing'}"
        gaps[key] = gaps.get(key, 0) + min(nxt, hi) - max(prev_end, lo)
    rank = lambda d: [[k, v / 1e9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}
