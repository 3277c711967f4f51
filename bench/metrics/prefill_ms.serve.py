"""Device busy time from the start of each traced ``generate`` call to its
first ``decode_step``: the prefill, the cache install and the first
token, averaged over the traced rounds; moves ``ttft_p95_ms``."""

from bench import devtrace


def read(r):
    tr = r["trace"]
    dev = tr.devices()[0]
    spans = tr.spans_named("bench.generate")
    runs = devtrace.runs_per_span(tr, dev, "jit_decode_step", "bench.generate")
    busy = [tr.busy(dev).within(a, rs[0][0])
            for (a, _), rs in zip(spans, runs) if rs]
    return sum(busy) / len(busy) / 1e6 if busy else None
