"""Kernel workloads — the repo's own Pallas kernels as first-class tunables.

ROADMAP item 2 ("close the loop"): the paper's tree-shaped search space so far
only tuned PolyBench-style einsum nests, while the serving stack ships real
Pallas kernels whose block sizes (``flash_attention``'s ``block_q``/
``block_kv``, ``ssd_scan``'s ``chunk``) are exactly the Tile transformation
applied to the kernel's loop nest.  A :class:`KernelWorkload` wraps such a
kernel behind the same duck-type surface as :class:`~repro.core.workloads.
Workload` — "any callable with a structure key":

* ``nest()`` — the kernel's loop nest over its *grid* dims (batch·head,
  sequence axes), with the per-element feature dims (head_dim, state size)
  folded into ``Access.elem_bytes`` so the cost model's working-set math is
  right without exposing untileable dims to the search;
* ``fingerprint()`` / ``scaled()`` / ``make_args()`` / ``reference()`` — the
  store/verification surface the evaluation engine and
  :class:`~repro.core.measure.PallasBackend` consume;
* ``kernel_params(nest)`` — map a transformed nest back onto the kernel's
  concrete block-size kwargs.  Schedules the kernel cannot express (tiling a
  head dim, multi-level tiling, a reordered grid, unroll/vectorize) raise
  :class:`~repro.core.codegen.CodegenError` and become red nodes, exactly
  like the paper's compile failures;
* ``build(nest, interpret=...)`` — a callable evaluating the kernel under
  that schedule (Mosaic, or interpret-mode Pallas), verified against the
  :mod:`repro.kernels.ref` oracle.

Instances are pure data (kernel behavior lives in a name-keyed registry
populated at import), so they pickle across the
:class:`~repro.core.measure.SupervisedPool` worker pipe and rebuild on the
worker side by importing this module — kernel tuning gets the same hard
deadlines, kill/respawn and async pipelining as every other backend.

Causal attention is modeled with the paper's triangular bound ``("q",
"kv")``: the conservative model-compiler rules (no kv tile wider than the q
tile, kv tiled only if q is) reproduce the syr2k-style red-node fraction on
a real kernel.  The winning schedule feeds back into serving via
:func:`serve_overrides` (``block_q`` → ``ModelConfig.attn_q_chunk``,
``chunk`` → ``ModelConfig.ssd_chunk``) so the end-to-end metric is
tokens/sec, not kernel microseconds.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field, replace
from typing import Callable

import numpy as np

from .codegen import CodegenError
from .loopnest import Access, LoopNest, make_nest


@dataclass(frozen=True)
class KernelDef:
    """Behavior of one registered kernel (the picklable
    :class:`KernelWorkload` holds only data + this registry key)."""

    loop_order: tuple[str, ...]         # fixed grid order of the kernel
    tileable: tuple[str, ...]           # dims with a block-size knob
    seq_vars: tuple[str, ...]           # dims ``scaled()`` shrinks
    nest: Callable[["KernelWorkload"], LoopNest]
    make_args: Callable[["KernelWorkload", int], dict]
    reference: Callable[["KernelWorkload", dict], "np.ndarray"]
    kernel_params: Callable[["KernelWorkload", LoopNest], dict]
    build: Callable[["KernelWorkload", LoopNest, bool], Callable]
    vmem_bytes: Callable[["KernelWorkload", LoopNest], int]


_KERNELS: dict[str, KernelDef] = {}


def register_kernel(name: str, kdef: KernelDef) -> None:
    _KERNELS[name] = kdef


def _kernel_def(name: str) -> KernelDef:
    kd = _KERNELS.get(name)
    if kd is None:
        raise ValueError(f"unknown kernel {name!r} "
                         f"(registered: {', '.join(sorted(_KERNELS))})")
    return kd


@dataclass(frozen=True)
class KernelWorkload:
    """A Pallas kernel as a tunable workload (see module docstring).

    ``extents`` are the grid-dim trip counts (e.g. ``h``/``q``/``kv`` for
    attention); ``params`` the static kernel configuration (head counts,
    feature dims, causal flag) that ``make_args``/``reference``/``build``
    consume.  Both are data — everything behavioral resolves through the
    kernel registry, keyed by ``kernel``.
    """

    kernel: str
    name: str
    extents: dict[str, int]
    params: dict = field(default_factory=dict)

    # -- identity --------------------------------------------------------------

    def fingerprint(self) -> str:
        """Stable short hash of everything determining measured semantics
        (same contract as :meth:`Workload.fingerprint` — the persistent
        store keys records by it)."""
        fp = self.__dict__.get("_fingerprint")
        if fp is None:
            payload = json.dumps(
                {
                    "kernel": self.kernel,
                    "name": self.name,
                    "extents": sorted(self.extents.items()),
                    "params": sorted(self.params.items()),
                },
                sort_keys=True,
                separators=(",", ":"),
            )
            fp = hashlib.sha256(payload.encode()).hexdigest()[:16]
            object.__setattr__(self, "_fingerprint", fp)
        return fp

    # -- loop-nest IR ----------------------------------------------------------

    def nest(self) -> LoopNest:
        return _kernel_def(self.kernel).nest(self)

    # -- scaling / concrete arrays ---------------------------------------------

    def scaled(self, scale: float) -> "KernelWorkload":
        """Shrink the *sequence* dims for fast interpret-mode verification.
        Head/batch grid dims keep their extent — heads are what GQA/grouping
        correctness depends on, and they are cheap."""
        kd = _kernel_def(self.kernel)
        ext = {
            v: (max(8, int(e * scale)) if v in kd.seq_vars else e)
            for v, e in self.extents.items()
        }
        return replace(self, extents=ext)

    def make_args(self, scale: float = 1.0, seed: int = 0) -> dict:
        w = self.scaled(scale) if scale != 1.0 else self
        return _kernel_def(self.kernel).make_args(w, seed)

    def reference(self, args: dict) -> "np.ndarray":
        return _kernel_def(self.kernel).reference(self, args)

    # -- schedule → kernel -----------------------------------------------------

    def kernel_params(self, nest: LoopNest) -> dict:
        """Concrete kernel kwargs for a transformed nest, or
        :class:`CodegenError` when the kernel cannot express the schedule
        (red node)."""
        return _kernel_def(self.kernel).kernel_params(self, nest)

    def build(self, nest: LoopNest, *, interpret: bool) -> Callable:
        """Traceable callable ``f(args) -> array`` running the kernel under
        the schedule ``nest`` encodes (Mosaic, or the Pallas interpreter
        when ``interpret``)."""
        return _kernel_def(self.kernel).build(self, nest, interpret)

    def vmem_bytes(self, nest: LoopNest) -> int:
        """VMEM working set of the schedule's blocks (tile-rejection
        analogue of :func:`repro.core.codegen.vmem_bytes`)."""
        return _kernel_def(self.kernel).vmem_bytes(self, nest)


# ---------------------------------------------------------------------------
# Shared schedule extraction: one tiling level per tileable grid dim, fixed
# grid order — the shape every kernel in this package exposes.
# ---------------------------------------------------------------------------


def _extract_blocks(kw: KernelWorkload, nest: LoopNest) -> dict[str, int]:
    """Per-var block sizes of a transformed nest (untiled var → full extent).

    Rejections (→ :class:`CodegenError` red nodes, paper §IV-B):
    tiling of a non-tileable dim, multi-level / strided tiling, a grid
    order the kernel's fixed ``pallas_call`` grid cannot realize, and
    unroll/vectorize (no such knob on these kernels).  ``Parallelize`` of a
    grid dim is accepted and ignored — Pallas grid dims are parallel by
    construction (the reduction dims are already fenced off by legality).
    """
    kd = _kernel_def(kw.kernel)
    per_var: dict[str, list] = {}
    for l in nest.loops:
        per_var.setdefault(l.origin, []).append(l)
        if l.unroll > 1 or l.vectorize:
            raise CodegenError(
                f"kernel {kw.kernel!r}: unroll/vectorize of {l.origin!r} "
                f"has no kernel knob")
    blocks: dict[str, int] = {}
    for v, ls in per_var.items():
        points = [l for l in ls if l.is_point]
        floors = [l for l in ls if not l.is_point]
        if v not in kd.tileable:
            if points:
                raise CodegenError(
                    f"kernel {kw.kernel!r}: dim {v!r} is not tileable "
                    f"(no block-size knob)")
            blocks[v] = nest.extents[v]
            continue
        # Stacked tilings split a var into >1 floor level (re-tiling the
        # point loop spawns a floor, not a second point — count both).
        if len(points) > 1 or len(floors) > 1:
            raise CodegenError(
                f"kernel {kw.kernel!r}: {v!r} tiled "
                f"{len(points) + len(floors) - 1}× — the kernel has a "
                f"single blocking level")
        if points and points[0].span != 1:
            raise CodegenError(
                f"kernel {kw.kernel!r}: strided tiling of {v!r} is not a "
                f"contiguous block")
        blocks[v] = points[0].trips if points else nest.extents[v]
    grid_order = []
    for l in nest.loops:
        if not l.is_point and l.origin not in grid_order:
            grid_order.append(l.origin)
    if tuple(grid_order) != kd.loop_order:
        raise CodegenError(
            f"kernel {kw.kernel!r}: grid order {tuple(grid_order)} is fixed "
            f"to {kd.loop_order} by the kernel's pallas_call")
    return blocks


# ---------------------------------------------------------------------------
# Flash attention: block_q / block_kv over the (h, q, kv) grid.
# ---------------------------------------------------------------------------


def _attn_nest(kw: KernelWorkload) -> LoopNest:
    d = kw.params["head_dim"]
    eb = 4 * d          # f32 rows of D elements folded into elem_bytes
    accesses = (
        Access("O", ("h", "q"), kind="reduce", elem_bytes=eb),
        Access("Q", ("h", "q"), kind="read", elem_bytes=eb),
        Access("K", ("h", "kv"), kind="read", elem_bytes=eb),
        Access("V", ("h", "kv"), kind="read", elem_bytes=eb),
    )
    return make_nest(
        kw.name, ("h", "q", "kv"), kw.extents, accesses,
        triangular=(("q", "kv"),) if kw.params.get("causal", True) else (),
        flops_per_point=4 * d,      # QKᵀ + PV: two 2·D-flop MACs per point
    )


def _attn_make_args(kw: KernelWorkload, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    p = kw.params
    b, hq, hkv, d = p["batch"], p["heads_q"], p["heads_kv"], p["head_dim"]
    sq, skv = kw.extents["q"], kw.extents["kv"]

    def norm(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    return {"Q": norm(b, hq, sq, d), "K": norm(b, hkv, skv, d),
            "V": norm(b, hkv, skv, d)}


def _attn_reference(kw: KernelWorkload, args: dict) -> "np.ndarray":
    from repro.kernels.ref import attention_ref

    return attention_ref(args["Q"], args["K"], args["V"],
                         causal=kw.params.get("causal", True))


def _attn_kernel_params(kw: KernelWorkload, nest: LoopNest) -> dict:
    blocks = _extract_blocks(kw, nest)
    return {"block_q": blocks["q"], "block_kv": blocks["kv"]}


def _attn_build(kw: KernelWorkload, nest: LoopNest,
                interpret: bool) -> Callable:
    import jax.numpy as jnp

    from repro.kernels.attention import flash_attention

    kp = kw.kernel_params(nest)
    causal = kw.params.get("causal", True)

    def run(args: dict):
        return flash_attention(
            jnp.asarray(args["Q"]), jnp.asarray(args["K"]),
            jnp.asarray(args["V"]), causal=causal, interpret=interpret,
            **kp)

    return run


def _attn_vmem_bytes(kw: KernelWorkload, nest: LoopNest) -> int:
    blocks = _extract_blocks(kw, nest)
    d = kw.params["head_dim"]
    bq = min(blocks["q"], kw.extents["q"])
    bkv = min(blocks["kv"], kw.extents["kv"])
    # q + k + v + out blocks, plus the (m, l, acc) f32 scratch
    return 4 * (bq * d + 2 * bkv * d + bq * d) + 4 * (2 * bq + bq * d)


register_kernel("attention", KernelDef(
    loop_order=("h", "q", "kv"),
    tileable=("q", "kv"),
    seq_vars=("q", "kv"),
    nest=_attn_nest,
    make_args=_attn_make_args,
    reference=_attn_reference,
    kernel_params=_attn_kernel_params,
    build=_attn_build,
    vmem_bytes=_attn_vmem_bytes,
))


def attention_workload(
    batch: int = 1,
    heads_q: int = 8,
    heads_kv: int = 2,
    seq_q: int = 2048,
    seq_kv: int = 2048,
    head_dim: int = 64,
    causal: bool = True,
    name: str | None = None,
) -> KernelWorkload:
    """The prefill flash-attention hot-spot as a tunable workload (GQA by
    default — grouping is the correctness-relevant part of the index map)."""
    if heads_q % heads_kv:
        raise ValueError(f"heads_q={heads_q} must be a multiple of "
                         f"heads_kv={heads_kv} (GQA grouping)")
    return KernelWorkload(
        kernel="attention",
        name=name or "flash_attention",
        extents={"h": batch * heads_q, "q": seq_q, "kv": seq_kv},
        params={"batch": batch, "heads_q": heads_q, "heads_kv": heads_kv,
                "head_dim": head_dim, "causal": bool(causal)},
    )


# ---------------------------------------------------------------------------
# Mamba-2 SSD scan: chunk length over the (h, l) grid.  The sequential state
# pass is modeled as a reduce access indexed by ``h`` only, so the ``l`` loop
# carries the recurrence and can never be parallelized (legality rule 1).
# ---------------------------------------------------------------------------


def _ssd_nest(kw: KernelWorkload) -> LoopNest:
    p_dim, n_dim = kw.params["proj"], kw.params["state"]
    accesses = (
        Access("H", ("h",), kind="reduce", elem_bytes=4 * n_dim * p_dim),
        Access("Y", ("h", "l"), kind="write", elem_bytes=4 * p_dim),
        Access("X", ("h", "l"), kind="read", elem_bytes=4 * p_dim),
        Access("DT", ("h", "l"), kind="read", elem_bytes=4),
        Access("B", ("h", "l"), kind="read", elem_bytes=4 * n_dim),
        Access("C", ("h", "l"), kind="read", elem_bytes=4 * n_dim),
    )
    return make_nest(
        kw.name, ("h", "l"), kw.extents, accesses,
        flops_per_point=6 * n_dim * p_dim,  # scores + y + state update MACs
    )


def _ssd_make_args(kw: KernelWorkload, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    bh, l = kw.extents["h"], kw.extents["l"]
    p_dim, n_dim = kw.params["proj"], kw.params["state"]
    return {
        "X": (0.1 * rng.standard_normal((bh, l, p_dim))).astype(np.float32),
        "DT": (0.1 + 0.5 * rng.random((bh, l, 1))).astype(np.float32),
        "A": (-1.0 - rng.random((bh, 1, 1))).astype(np.float32),
        "B": (rng.standard_normal((bh, l, n_dim)) / 4).astype(np.float32),
        "C": rng.standard_normal((bh, l, n_dim)).astype(np.float32),
    }


def _ssd_reference(kw: KernelWorkload, args: dict) -> "np.ndarray":
    """The literal recurrence (slowest, most obviously correct oracle),
    re-laid-out: the kernel's flat (BH, L, ·) arrays become the reference's
    (L, H, ·) with one B/C group per head."""
    import jax.numpy as jnp

    from repro.kernels.ref import ssd_ref_recurrent

    ys, _ = ssd_ref_recurrent(
        jnp.asarray(np.transpose(args["X"], (1, 0, 2))),
        jnp.asarray(args["DT"][:, :, 0].T),
        jnp.asarray(args["A"][:, 0, 0]),
        jnp.asarray(np.transpose(args["B"], (1, 0, 2))),
        jnp.asarray(np.transpose(args["C"], (1, 0, 2))),
    )
    return jnp.transpose(ys, (1, 0, 2))


def _ssd_kernel_params(kw: KernelWorkload, nest: LoopNest) -> dict:
    blocks = _extract_blocks(kw, nest)
    return {"chunk": blocks["l"]}


def _ssd_build(kw: KernelWorkload, nest: LoopNest,
               interpret: bool) -> Callable:
    import jax.numpy as jnp

    from repro.kernels.ssd import ssd_scan

    kp = kw.kernel_params(nest)

    def run(args: dict):
        return ssd_scan(
            jnp.asarray(args["X"]), jnp.asarray(args["DT"]),
            jnp.asarray(args["A"]), jnp.asarray(args["B"]),
            jnp.asarray(args["C"]), interpret=interpret, **kp)

    return run


def _ssd_vmem_bytes(kw: KernelWorkload, nest: LoopNest) -> int:
    blocks = _extract_blocks(kw, nest)
    p_dim, n_dim = kw.params["proj"], kw.params["state"]
    ch = min(blocks["l"], kw.extents["l"])
    # x + dt + b + c + y blocks, the (N, P) state scratch, and the (ch, ch)
    # intra-chunk decay/score tiles the kernel materializes
    return (4 * ch * (2 * p_dim + 2 * n_dim + 1)
            + 4 * n_dim * p_dim + 4 * 2 * ch * ch)


register_kernel("ssd", KernelDef(
    loop_order=("h", "l"),
    tileable=("l",),
    seq_vars=("l",),
    nest=_ssd_nest,
    make_args=_ssd_make_args,
    reference=_ssd_reference,
    kernel_params=_ssd_kernel_params,
    build=_ssd_build,
    vmem_bytes=_ssd_vmem_bytes,
))


def ssd_workload(
    heads: int = 8,
    seq: int = 2048,
    proj: int = 64,
    state: int = 64,
    name: str | None = None,
) -> KernelWorkload:
    """The Mamba-2 SSD chunked scan as a tunable workload — ``chunk`` is
    literally a single-level Tile of the sequence loop."""
    return KernelWorkload(
        kernel="ssd",
        name=name or "ssd_scan",
        extents={"h": heads, "l": seq},
        params={"proj": proj, "state": state},
    )


KERNEL_WORKLOAD_BUILDERS: dict[str, Callable[..., KernelWorkload]] = {
    "attention": attention_workload,
    "ssd": ssd_workload,
}


def kernel_workload(kind: str, **kwargs) -> KernelWorkload:
    """Build a kernel workload by name — the :class:`~repro.core.session.
    TuningSpec` resolution hook (``workload: "attention"`` / ``"ssd"``)."""
    builder = KERNEL_WORKLOAD_BUILDERS.get(kind)
    if builder is None:
        raise ValueError(
            f"unknown kernel workload {kind!r} "
            f"(known: {', '.join(sorted(KERNEL_WORKLOAD_BUILDERS))})")
    return builder(**kwargs)


# ---------------------------------------------------------------------------
# Feeding the winning schedule back into serving.
# ---------------------------------------------------------------------------


def serve_overrides(kernel: str, kernel_params: dict) -> dict:
    """Map a tuned kernel schedule onto the :class:`~repro.configs.base.
    ModelConfig` knobs the serving stack reads (``attn_q_chunk`` drives the
    blockwise prefill attention in models/layers.py, ``ssd_chunk`` the
    Mamba-2 mixer) — how a tuned block size becomes end-to-end tokens/sec."""
    if kernel == "attention":
        return {"attn_q_chunk": int(kernel_params["block_q"])}
    if kernel == "ssd":
        return {"ssd_chunk": int(kernel_params["chunk"])}
    raise ValueError(f"no serving knob mapping for kernel {kernel!r}")
