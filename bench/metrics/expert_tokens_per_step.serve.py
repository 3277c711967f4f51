"""Tokens one held expert computes per layer and decode step: the
``expert_rows`` of the traced calls' ``serve.emit`` spans that lie between
two of their ``serve.dispatch`` spans, over the sum of their
``expert_slots`` (expert layers × experts held); the program's spans alone
give it, and a program that keeps no such count reads nothing.  Moves
``itl_p95_ms``."""

from bench import harness, progspans


def read(r):
    got = progspans.traced_calls(r["trace"])
    if got is None:
        return None
    rows = slots = 0
    for kids in got[1]:
        emits = kids.get("serve.emit", [])
        if len(emits) != len(kids.get("serve.dispatch", [])) + 1:
            harness.log("[expert_tokens] emits and dispatches do not "
                        "alternate")
            return None
        for s in emits[1:-1]:
            if "expert_rows" not in s.args:
                harness.log("[expert_tokens] the program counts no expert "
                            "rows")
                return None
            rows += s.args["expert_rows"]
            slots += s.args["expert_slots"]
    return rows / slots if slots else None
