"""Share of the decode slots the engine stepped that carried a token a
request asked for: tokens that came from decode steps (each request's
tokens after its first, which the prefill gives) over the traced rounds,
over the traced ``decode_step`` executions times the engine's
``max_batch``.  Moves ``output_tokens_per_s``."""

from bench import devtrace


def read(r):
    tr, rec = r["trace"], r["record"]
    steps = len(devtrace.runs(tr, tr.devices()[0], "jit_decode_step"))
    if not steps:
        return None
    decoded = sum(max(len(q.out) - 1, 0)
                  for rd in rec["rounds"][:rec["trace_rounds"]]
                  for q in rd.requests)
    return 100.0 * decoded / (steps * r["workload"]["engine"]["max_batch"])
