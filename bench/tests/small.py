"""Small configurations and cells for the CPU tests: the benchmark's own
files with every width cut, so that a test runs in seconds."""

from __future__ import annotations

import time

from bench import harness

SIZES = {
    "dense_decoder": dict(hidden_size=64, intermediate_size=128,
                          num_attention_heads=4, num_key_value_heads=2,
                          num_hidden_layers=2, vocab_size=256),
    "mamba2": dict(hidden_size=64, num_hidden_layers=2, vocab_size=256,
                   state_size=16, head_dim=16, num_heads=8, chunk_size=16),
}
CELLS = ["internlm2-1.8b.chat", "mamba2-130m.chat"]


def config(cell: str, **program) -> dict:
    """The cell's configuration file with small sizes (and, optionally,
    program fields such as ``dtype`` overridden)."""
    cfg = harness.load_json("configs", cell.rsplit(".", 1)[0] + ".json")
    cfg["config"].update(SIZES[cfg["architecture"]])
    cfg["schedules"] = None
    cfg["program"] = program
    return cfg


def workload(cell: str) -> dict:
    wl = harness.load_json("workloads", cell + ".json")
    wl["traffic"].update(
        clients=3, prompt_len={"values": [16, 32], "weights": [0.5, 0.5]},
        output_len={"values": [3, 6], "weights": [0.5, 0.5]})
    wl["engine"] = {"max_batch": 3, "max_seq": 40}
    # the limit of a full-width cell does not carry to these widths.  Here
    # the widest gap swings with near-ties of a 256-token head (0 to 0.066
    # over seeds), so the rehearsal limits the mean: the program reads
    # under 0.008, and a token altered reads over 0.1
    wl["check"] = {"tokens": 12, "limits": {"served_token_gap_mean": 0.02}}
    return wl


def context(cell: str, seed: int = 2**33 + 5, seconds: float = 0.5,
            **program) -> harness.Context:
    return harness.Context({"name": cell}, workload(cell),
                           config(cell, **program), seed, seconds, False,
                           time.perf_counter())
