"""The traffic generator: a seed gives the same requests every time, the
lengths follow the stated weights, every prompt of a round has one length,
and every seed does the same work."""

from __future__ import annotations

from collections import Counter

import pytest

from bench import harness
from bench.traffic import ClosedLoop, apportion, spread_evenly

MIX = {"clients": 8, "cycle": 4,
       "prompt_len": {"values": [256, 512, 1024, 2048],
                      "weights": [0.3, 0.3, 0.25, 0.15]},
       "output_len": {"values": [64, 128, 256],
                      "weights": [0.45, 0.35, 0.2]}}


def _rounds(seed, n=3, **mix):
    loop = ClosedLoop({**MIX, **mix}, 92544, seed)
    return [loop.next_round() for _ in range(n)]


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**40 + 1, -3])
def test_the_same_seed_gives_the_same_requests(seed):
    assert _rounds(seed) == _rounds(seed)
    assert _rounds(seed) != _rounds(seed + 1)


def test_apportion_by_largest_remainder():
    assert apportion([0.3, 0.3, 0.25, 0.15], 8) == [3, 2, 2, 1]
    assert apportion([0.45, 0.35, 0.2], 8) == [4, 3, 1]
    assert apportion([0.4, 0.3, 0.2, 0.1], 64) == [26, 19, 13, 6]
    assert sum(apportion([1, 1, 1], 10)) == 10


@pytest.mark.parametrize("seed", [1, 2**33])
def test_stratified_rounds_hold_the_same_lengths_in_another_order(seed):
    rounds = _rounds(seed, n=8)
    assert [len(rnd[0][0]) for rnd in rounds] == [256, 512, 1024, 2048] * 2
    for rnd in rounds:
        assert len({len(p) for p, _ in rnd}) == 1
        assert Counter(o for _, o in rnd) == {64: 4, 128: 3, 256: 1}
        assert all(1 <= t < 92544 for p, _ in rnd for t in p)
    a, b = _rounds(seed, n=1)[0], _rounds(seed + 1, n=1)[0]
    assert [o for _, o in a] != [o for _, o in b]
    assert [len(p) for p, _ in a] == [len(p) for p, _ in b]


def test_a_cycle_of_rounds_follows_the_weights():
    loop = ClosedLoop({**MIX, "cycle": 20}, 100, 0)
    assert Counter(loop.prompt_cycle) == {256: 6, 512: 6, 1024: 5, 2048: 3}


def test_spread_evenly_spaces_each_values_turns():
    assert spread_evenly([64, 128, 256, 512], [4, 3, 2, 1]) == [
        64, 128, 256, 64, 128, 512, 64, 256, 128, 64]
    assert spread_evenly(["a", "b"], [1, 1]) == ["a", "b"]
    assert spread_evenly(["a", "b"], [0, 3]) == ["b", "b", "b"]


def test_longest_prompts_to_warm_up():
    assert ClosedLoop(MIX, 100, 0).prompt_lengths() == [256, 512, 1024, 2048]
    assert ClosedLoop({**MIX, "cycle": 1}, 100, 0).prompt_lengths() == [256]


@pytest.mark.parametrize("cell", ["internlm2-1.8b.chat", "mamba2-130m.chat"])
def test_every_cell_file_names_a_mix_the_generator_reads(cell):
    wl = harness.load_json("workloads", f"{cell}.json")
    loop = ClosedLoop(wl["traffic"], 1000, 0)
    rnd = loop.next_round()
    assert len(rnd) == wl["traffic"]["clients"] <= wl["engine"]["max_batch"]
    assert set(loop.prompt_lengths()) == set(wl["traffic"]["prompt_len"]["values"])
    longest = max(wl["traffic"]["prompt_len"]["values"]) + max(
        wl["traffic"]["output_len"]["values"])
    assert longest <= wl["engine"]["max_seq"]
