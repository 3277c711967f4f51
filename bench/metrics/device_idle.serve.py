"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals / window); moves
``output_tokens_per_s``."""

from bench import devtrace


def read(r):
    busy, window = devtrace.busy_share(r["trace"])
    return 100.0 * (1.0 - busy / window)
