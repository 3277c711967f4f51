"""Pallas TPU kernels for the performance-critical compute layers.

Each kernel has explicit ``BlockSpec`` VMEM tiling whose block sizes are the
paper's tile-size parameters (tuned by ``repro.core``), a jit'd wrapper in
:mod:`repro.kernels.ops`, and a pure-jnp oracle in :mod:`repro.kernels.ref`.
Every call says how it runs: ``interpret=False`` compiles with Mosaic and
needs a TPU, ``interpret=True`` runs the Pallas interpreter (how the CPU
tests validate them).
"""

from .ops import covariance, flash_attention, matmul, ssd_scan, syr2k

__all__ = ["covariance", "flash_attention", "matmul", "ssd_scan", "syr2k"]
