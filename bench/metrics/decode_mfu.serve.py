"""The model operations of the traced ``decode_step`` executions over
their device time and the chip's bf16 peak.  The operations come from the
shapes (``bench.roofline``): each step of a round decodes every slot, at a
context one longer than the step before, starting from the round's padded
prompt.  Moves ``output_tokens_per_s``."""

from bench import devtrace, harness, roofline


def read(r):
    tr, rec = r["trace"], r["record"]
    step = roofline.DECODE_STEPS[r["architecture"]]
    per_round = devtrace.runs_per_span(tr, tr.devices()[0],
                                       "jit_decode_step", "bench.generate")
    rounds = rec["rounds"][:rec["trace_rounds"]]
    flops = secs = 0.0
    for rd, runs in zip(rounds, per_round):
        steps = max(len(q.out) for q in rd.requests) - 1
        if steps != len(runs):
            harness.log(f"[decode_mfu] a round made {steps} decode steps but "
                        f"the trace holds {len(runs)}")
            return None
        batch = len(rd.requests)
        flops += sum(step(r["config"], batch, rd.plen + j + 1)[0]
                     for j in range(steps))
        secs += sum(e - s for s, e in runs) / 1e9
    if not secs:
        return None
    return 100.0 * flops / (secs * r["peak"]["bf16_flops"])
