"""Device idle inside the engine's ``serve.emit`` spans (each live slot's
token and the position pulled to the host, the stop checks) within the
gaps of ``decode_gap_ms.serve``, per gap; moves ``itl_p95_ms``."""

from bench import progspans


def read(r):
    return progspans.idle_ms_per_gap(r["trace"], "serve.emit")
