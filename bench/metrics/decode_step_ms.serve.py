"""Device time of one execution of the jitted ``decode_step``, averaged
over the traced window; moves ``itl_p95_ms``."""

from bench import devtrace


def read(r):
    tr = r["trace"]
    runs = devtrace.runs(tr, tr.devices()[0], "jit_decode_step")
    if not runs:
        return None
    return sum(e - s for s, e in runs) / len(runs) / 1e6
