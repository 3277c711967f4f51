"""The one traffic generator: reads a mix from a data file and makes the
requests of a run from its seed.

A mix gives the number of clients of a closed loop, the lengths of prompts
and outputs as discrete values with weights, and ``cycle``, a number of
rounds.  A round is one request from each client, and every prompt of a
round has one length: the engine pads a round to its longest prompt and
masks nothing, so only a round of equal lengths gives each request its own
answer (PERF.md, Open questions).  The prompt lengths are apportioned over
the rounds of a cycle by their weights, and spread evenly over it; the
output lengths are apportioned over the clients of every round.  The seed
chooses which client gets which output length, and every token id, so every
seed does the same work in another order.  Token ids are uniform over
``[1, vocab)``.
"""

from __future__ import annotations

import numpy as np


def seed_sequence(seed: int, *stream: int) -> np.random.SeedSequence:
    """A numpy seed sequence for any whole number, negative or beyond 64
    bits, and a stream index."""
    return np.random.SeedSequence([seed % 2**64, *stream])


def apportion(weights: list[float], n: int) -> list[int]:
    """Split ``n`` slots in proportion to ``weights`` by largest remainder
    (ties go to the earlier value)."""
    total = float(sum(weights))
    exact = [w / total * n for w in weights]
    counts = [int(e) for e in exact]
    order = sorted(range(len(weights)), key=lambda i: (counts[i] - exact[i], i))
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def spread_evenly(values: list, counts: list[int]) -> list:
    """``values[i]`` ``counts[i]`` times, each value's turns spaced evenly
    over the sequence (ties in the listed order)."""
    slots = [((j + 0.5) / c, i) for i, c in enumerate(counts)
             for j in range(c)]
    return [values[i] for _, i in sorted(slots)]


class ClosedLoop:
    """Rounds of requests of a closed loop: each client sends its next
    request when its reply comes back."""

    def __init__(self, mix: dict, vocab: int, seed: int, stream: int = 0):
        self.clients = int(mix["clients"])
        p = mix["prompt_len"]
        self.prompt_cycle = spread_evenly(
            p["values"], apportion(p["weights"], int(mix["cycle"])))
        o = mix["output_len"]
        self.outputs = np.repeat(o["values"],
                                 apportion(o["weights"], self.clients))
        self.vocab = vocab
        self.rng = np.random.default_rng(seed_sequence(seed, 1, stream))
        self.n = 0

    def prompt_lengths(self) -> list[int]:
        """Every prompt length a round can have: the shapes to warm up."""
        return sorted(set(self.prompt_cycle))

    def prompts_of_length(self, plen: int) -> list[list[int]]:
        """One prompt of ``plen`` tokens for each client."""
        return [self.rng.integers(1, self.vocab, plen).tolist()
                for _ in range(self.clients)]

    def next_round(self) -> list[tuple[list[int], int]]:
        """One round: (prompt token ids, output length) for each client."""
        plen = int(self.prompt_cycle[self.n % len(self.prompt_cycle)])
        self.n += 1
        return [(p, int(o)) for p, o in zip(self.prompts_of_length(plen),
                                             self.rng.permutation(self.outputs))]
