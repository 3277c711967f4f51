"""Device idle inside the engine's ``serve.dispatch`` spans (one decode
step dispatched, its argmax and the position's increment) within the gaps
of ``decode_gap_ms.serve``, per gap; moves ``itl_p95_ms``."""

from bench import progspans


def read(r):
    return progspans.idle_ms_per_gap(r["trace"], "serve.dispatch")
