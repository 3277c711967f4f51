"""Device idle inside the engine's ``serve.prefill`` span (the padded
tokens built, the eager prefill, the caches made and installed, the first
argmax dispatched), per traced round; moves ``ttft_p95_ms``."""

from bench import progspans


def read(r):
    return progspans.prefill_idle_ms(r["trace"])
