"""Device-to-host pulls the engine makes between two decode steps: the
``syncs`` of the traced calls' ``serve.emit`` spans that lie between two of
their ``serve.dispatch`` spans, per such gap; the program's spans alone
give it.  Moves ``itl_p95_ms``."""

from bench import progspans


def read(r):
    return progspans.syncs_per_step(r["trace"])
