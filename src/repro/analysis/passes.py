"""Pass-manager core: named analysis passes → :class:`Verdict` with provenance.

Each pass is a function ``(ctx: AnalysisContext) -> iterable[Finding]``
registered by name.  :class:`StaticAnalyzer` selects the passes that apply to
a (workload, backend) pair and runs them over a transformed nest; an empty
finding list means *statically feasible* (the backend may still reject it —
coverage is measured by the differential harness, soundness is the invariant).

Two pass families:

* ``dependence.*`` — legality from the dependence evidence of
  :mod:`repro.analysis.deps`.  These must be exactly equivalent to
  ``repro.core.legality.check_legal`` (the hand-coded oracle): every backend
  runs ``check_legal`` before measuring, so equivalence gives soundness for
  free and the differential harness checks it pass-by-pass.
* ``feasibility.*`` — static mirrors of the backends' *deterministic*
  ``CodegenError`` red-node conditions: plan extraction (tiling a floor
  loop), the wallclock grid-step budget on the *scaled* nest, VMEM capacity
  vs the Pallas budget, kernel expressibility (stacked tilings, reordered
  grids, head-dim tiles), and the reduced-scale verification retiling
  (non-dividing spans after tile clamping).  Each mirror calls the *same*
  production helpers (``codegen.vmem_bytes``, ``_extract_plan``,
  ``_retile_to``, ``kernel_params``) so the prediction cannot drift from the
  backend it models.

Soundness rule for every pass: reject only what the modeled backend
*deterministically* rejects.  Never predict nondeterministic failures
(timeouts, interpret/oracle mismatches) — those stay measured.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Iterable

from repro.core import codegen
from repro.core.codegen import MAX_WALLCLOCK_GRID_STEPS
from repro.core.loopnest import LoopNest
from repro.core.measure import _is_kernel_workload, _retile_to
from repro.core.transformations import TransformError

from .deps import Dependence, dependences

__all__ = [
    "AnalysisContext",
    "BackendModel",
    "Finding",
    "StaticAnalyzer",
    "Verdict",
    "available_passes",
    "register_pass",
]


@dataclass(frozen=True)
class Finding:
    """One rule violation: which rule fired, with what evidence, and the
    :class:`~repro.core.measure.Result` status the modeled backend would
    report for it."""

    rule: str            # registered pass name that produced it
    status: str          # "illegal" | "compile_error"
    detail: str          # human-readable reason (mirrors the backend's note)
    evidence: tuple = () # Dependences / numbers backing the verdict


@dataclass(frozen=True)
class Verdict:
    """Outcome of running the selected passes over one nest."""

    feasible: bool
    findings: tuple[Finding, ...] = ()
    passes_run: tuple[str, ...] = ()

    @property
    def rule(self) -> str | None:
        return self.findings[0].rule if self.findings else None

    @property
    def status(self) -> str | None:
        return self.findings[0].status if self.findings else None

    @property
    def detail(self) -> str | None:
        return self.findings[0].detail if self.findings else None


@dataclass(frozen=True)
class BackendModel:
    """The static view of a measurement backend: just the knobs that decide
    its deterministic red nodes.  ``of`` unwraps fault-injection wrappers —
    injection never turns a red result green, so the inner backend's
    deterministic conditions survive wrapping."""

    kind: str                       # "costmodel" | "wallclock" | "pallas" | "generic"
    scale: float = 1.0
    vmem_limit: int | None = None
    verify: bool = False
    interpret: bool = False         # pallas: scaled interpret-mode verify

    @classmethod
    def of(cls, backend) -> "BackendModel":
        b = backend
        seen = 0
        while getattr(b, "inner", None) is not None and seen < 8:
            b = b.inner
            seen += 1
        kind = getattr(b, "name", "generic")
        if kind == "costmodel":
            return cls(kind="costmodel")
        if kind == "wallclock":
            return cls(kind="wallclock", scale=getattr(b, "scale", 0.25))
        if kind == "pallas":
            return cls(
                kind="pallas",
                scale=getattr(b, "scale", 0.05),
                vmem_limit=getattr(b, "vmem_limit", 128 * 1024 * 1024),
                verify=getattr(b, "verify", True),
                interpret=getattr(b, "interpret", False),
            )
        return cls(kind="generic")


@dataclass
class AnalysisContext:
    """Everything a pass may look at.  ``config`` is optional — backend
    mirrors that replay the schedule against scaled extents (wallclock) need
    it; dependence passes only read ``nest``."""

    workload: object
    nest: LoopNest
    config: object | None = None
    backend: BackendModel = field(default_factory=lambda: BackendModel("generic"))
    _deps: tuple[Dependence, ...] | None = None

    @property
    def deps(self) -> tuple[Dependence, ...]:
        if self._deps is None:
            self._deps = dependences(self.nest)
        return self._deps


PassFn = Callable[[AnalysisContext], Iterable[Finding]]
_PASSES: dict[str, PassFn] = {}


def register_pass(name: str) -> Callable[[PassFn], PassFn]:
    def deco(fn: PassFn) -> PassFn:
        if name in _PASSES:
            raise ValueError(f"analysis pass {name!r} already registered")
        _PASSES[name] = fn
        return fn
    return deco


def available_passes() -> tuple[str, ...]:
    return tuple(sorted(_PASSES))


# ---------------------------------------------------------------------------
# Dependence passes (legality — must match check_legal exactly)
# ---------------------------------------------------------------------------


@register_pass("dependence.parallel-reduction")
def _parallel_reduction(ctx: AnalysisContext) -> Iterable[Finding]:
    """A thread-parallelized loop must not carry a reduction dependence
    (check_legal rule 1, from dependence evidence)."""
    carried = {d.var: d for d in ctx.deps if d.kind == "reduction"}
    for l in ctx.nest.loops:
        if l.parallel and l.origin in carried:
            d = carried[l.origin]
            yield Finding(
                rule="dependence.parallel-reduction",
                status="illegal",
                detail=(f"loop {l.name} (origin {l.origin}) carries "
                        f"{d.describe()} and cannot be thread-parallelized"),
                evidence=(d,),
            )


@register_pass("dependence.triangular")
def _triangular(ctx: AnalysisContext) -> Iterable[Finding]:
    """Bound-dependence rules for triangular pairs (check_legal rules 2a–2c,
    from the structural relation of the pair's transformed loops)."""
    nest = ctx.nest
    order = [l.name for l in nest.loops]
    for d in ctx.deps:
        if d.kind != "bound":
            continue
        provider, dependent = d.provider, d.var
        prov = [l for l in nest.loops if l.origin == provider]
        dep = [l for l in nest.loops if l.origin == dependent]
        # 2a: bound exchange needs skewing.
        if order.index(dep[0].name) < order.index(prov[0].name):
            yield Finding(
                rule="dependence.triangular",
                status="illegal",
                detail=(f"{d.describe()}: loop of {dependent!r} ordered "
                        f"before its bound provider (needs loop skewing)"),
                evidence=(d, "order"),
            )
            continue
        # 2b: dependent point loop hoisted above a provider floor loop.
        prov_floor_last = max(
            (order.index(l.name) for l in prov if not l.is_point), default=-1)
        dep_point_first = min(
            (order.index(l.name) for l in dep if l.is_point), default=len(order))
        if dep_point_first < prov_floor_last:
            yield Finding(
                rule="dependence.triangular",
                status="illegal",
                detail=(f"{d.describe()}: point loop of {dependent!r} hoisted "
                        f"above a floor loop of {provider!r}"),
                evidence=(d, "hoist"),
            )
            continue
        # 2c: tiling balance across the pair, aligned level by level; the
        # dependent must not be tiled wider, alone, or deeper than its
        # provider (unmatched inner levels have no bounding tile).
        prov_pts = [l.trips for l in prov if l.is_point]
        dep_pts = [l.trips for l in dep if l.is_point]
        bad = None
        for ps, ds in zip(prov_pts, dep_pts):
            if ds > ps:
                bad = f"tile {ds} wider than provider tile {ps}"
                break
        if bad is None and dep_pts and not prov_pts:
            bad = "tiled while its bound provider is not"
        if bad is None and len(dep_pts) > len(prov_pts) > 0:
            bad = (f"tiled {len(dep_pts)}× vs provider {len(prov_pts)}× — "
                   f"unmatched inner level(s) have no bounding tile")
        if bad is not None:
            yield Finding(
                rule="dependence.triangular",
                status="illegal",
                detail=f"{d.describe()}: {dependent!r} {bad}",
                evidence=(d, tuple(prov_pts), tuple(dep_pts)),
            )


# ---------------------------------------------------------------------------
# Feasibility passes (backend mirrors)
# ---------------------------------------------------------------------------


@register_pass("feasibility.xla")
def _xla(ctx: AnalysisContext) -> Iterable[Finding]:
    """Mirror of the wallclock backend's deterministic pipeline.  The backend
    ignores nest hints and re-derives the schedule against *scaled* extents
    (``WallclockBackend.evaluate``), so the mirror must too: a tile can
    exceed a scaled extent (TransformError) or the scaled grid can bust the
    step budget even when the full-scale nest would not — and vice versa."""
    if ctx.config is None:
        return
    w = ctx.workload.scaled(ctx.backend.scale)
    try:
        nest_s = ctx.config.apply(w.nest())
    except TransformError as e:
        yield Finding(
            rule="feasibility.xla", status="compile_error",
            detail=f"schedule does not derive at scale {ctx.backend.scale}: {e}",
            evidence=(ctx.backend.scale,),
        )
        return
    try:
        plan = codegen._extract_plan(w, nest_s)
    except codegen.CodegenError as e:
        yield Finding(
            rule="feasibility.xla", status="compile_error",
            detail=str(e), evidence=("plan",),
        )
        return
    grid_steps = 1
    for _v, trips, _span in plan.grid:
        grid_steps *= trips
    if grid_steps > MAX_WALLCLOCK_GRID_STEPS:
        yield Finding(
            rule="feasibility.xla", status="compile_error",
            detail=(f"grid of {grid_steps} steps exceeds wallclock budget "
                    f"({MAX_WALLCLOCK_GRID_STEPS})"),
            evidence=(grid_steps,),
        )


def _verified(w, nest, model: BackendModel):
    """The (workload, nest, scale) ``PallasBackend`` verifies: reduced and
    retiled in interpret mode, the full schedule under Mosaic."""
    if model.interpret:
        ws = w.scaled(model.scale)
        return ws, _retile_to(nest, ws), model.scale
    return w, nest, 1.0


@register_pass("feasibility.pallas")
def _pallas(ctx: AnalysisContext) -> Iterable[Finding]:
    """Mirror of ``PallasBackend._measure`` for einsum workloads: plan
    extraction + VMEM budget, and — when the backend verifies — the
    reduced-scale retiling's BlockSpec constraints (tile clamping can make a
    floor span stop dividing by its block width)."""
    w, nest, model = ctx.workload, ctx.nest, ctx.backend
    try:
        vmem = codegen.vmem_bytes(w, nest)
    except codegen.CodegenError as e:
        yield Finding(
            rule="feasibility.pallas", status="compile_error",
            detail=str(e), evidence=("plan",),
        )
        return
    if model.vmem_limit is not None and vmem > model.vmem_limit:
        yield Finding(
            rule="feasibility.pallas", status="compile_error",
            detail=f"BlockSpec tiles exceed VMEM ({vmem} B)",
            evidence=(vmem, model.vmem_limit),
        )
        return
    if model.verify:
        ws, nest_v, scale = _verified(w, nest, model)
        try:
            plan = codegen._extract_plan(ws, nest_v)
            for v, _trips, span in plan.grid:
                if span % plan.tile[v] != 0:
                    yield Finding(
                        rule="feasibility.pallas", status="compile_error",
                        detail=(f"var {v!r}: floor span {span} not a multiple "
                                f"of its block width {plan.tile[v]} at "
                                f"verification scale {scale}"),
                        evidence=(v, span, plan.tile[v]),
                    )
                    return
        except codegen.CodegenError as e:
            yield Finding(
                rule="feasibility.pallas", status="compile_error",
                detail=f"at verification scale {scale}: {e}",
                evidence=("verify-plan",),
            )


@register_pass("feasibility.kernel")
def _kernel(ctx: AnalysisContext) -> Iterable[Finding]:
    """Mirror of ``PallasBackend._measure`` for kernel workloads (the repo's
    hand-written Pallas kernels): the kernel's own expressibility conditions
    (stacked tilings, reordered grids, non-tileable dims, unroll/vectorize)
    raise through ``vmem_bytes``/``kernel_params``, plus the VMEM budget."""
    w, nest, model = ctx.workload, ctx.nest, ctx.backend
    try:
        vmem = w.vmem_bytes(nest)
    except codegen.CodegenError as e:
        yield Finding(
            rule="feasibility.kernel", status="compile_error",
            detail=str(e), evidence=("blocks",),
        )
        return
    if model.vmem_limit is not None and vmem > model.vmem_limit:
        yield Finding(
            rule="feasibility.kernel", status="compile_error",
            detail=f"BlockSpec tiles exceed VMEM ({vmem} B)",
            evidence=(vmem, model.vmem_limit),
        )
        return
    if model.verify:
        ws, nest_v, scale = _verified(w, nest, model)
        try:
            ws.kernel_params(nest_v)
        except codegen.CodegenError as e:
            yield Finding(
                rule="feasibility.kernel", status="compile_error",
                detail=f"at verification scale {scale}: {e}",
                evidence=("verify-blocks",),
            )


# ---------------------------------------------------------------------------
# The analyzer
# ---------------------------------------------------------------------------

_DEP_PASSES = ("dependence.parallel-reduction", "dependence.triangular")


def default_passes(workload, model: BackendModel) -> tuple[str, ...]:
    """Passes that soundly apply to (workload, backend).  Dependence passes
    always apply — every backend calls ``check_legal``.  Feasibility passes
    only when the backend actually enforces the mirrored condition."""
    names = list(_DEP_PASSES)
    kernel = _is_kernel_workload(workload)
    if model.kind == "wallclock" and not kernel:
        names.append("feasibility.xla")
    elif model.kind == "pallas":
        names.append("feasibility.kernel" if kernel else "feasibility.pallas")
    return tuple(names)


class StaticAnalyzer:
    """Runs the selected passes over transformed nests for one (workload,
    backend) pair.  ``analyze`` returns a :class:`Verdict`; infeasible means
    the modeled backend would deterministically reject the schedule."""

    def __init__(self, workload, backend=None, passes=None):
        self.workload = workload
        self.model = (backend if isinstance(backend, BackendModel)
                      else BackendModel.of(backend) if backend is not None
                      else BackendModel("generic"))
        names = tuple(passes) if passes is not None else default_passes(
            workload, self.model)
        unknown = [n for n in names if n not in _PASSES]
        if unknown:
            raise ValueError(f"unknown analysis pass(es): {unknown}")
        self.passes = names

    def analyze(self, nest: LoopNest, config=None) -> Verdict:
        ctx = AnalysisContext(
            workload=self.workload, nest=nest, config=config,
            backend=self.model,
        )
        findings: list[Finding] = []
        for name in self.passes:
            findings.extend(_PASSES[name](ctx))
        return Verdict(
            feasible=not findings,
            findings=tuple(findings),
            passes_run=self.passes,
        )
