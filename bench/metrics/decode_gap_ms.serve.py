"""Device idle time between two consecutive executions of the jitted
``decode_step`` within one ``generate`` call, summed over the traced window
and divided by the number of such gaps; moves ``itl_p95_ms``."""

from bench import devtrace


def read(r):
    tr = r["trace"]
    dev = tr.devices()[0]
    idle = n = 0
    for runs in devtrace.runs_per_span(tr, dev, "jit_decode_step",
                                       "bench.generate"):
        for (_, a), (b, _) in zip(runs, runs[1:]):
            idle += (b - a) - tr.busy(dev).within(a, b)
            n += 1
    return idle / n / 1e6 if n else None
