"""A CPU rehearsal of the closed-loop serving driver at small sizes: its
runs come out correct, a token altered where the engine produces it comes
out not correct, and the float8 control reads far above the program."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.drivers import serve_closed
from bench.tests import small


@pytest.mark.parametrize("cell", small.CELLS)
def test_a_rehearsal_serves_every_request_correctly(cell):
    out = serve_closed.run(small.context(cell))
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted >= 3
    assert out.checks["served_token_gap_mean"]["value"] < 0.02
    assert set(out.metrics) == {"output_tokens_per_s", "ttft_p95_ms",
                                "itl_p95_ms", "setup_s"}
    assert out.memory_peak_bytes == 0 and out.trace is None


def _alter_first_decoded_token(monkeypatch):
    """In every round, the first decode step hands back negated logits, so
    each request's second token is the one its logits rank last."""
    from repro.serve.engine import ServeEngine

    generate = ServeEngine.generate

    def broken(self, requests):
        decode, calls = self._decode, []

        def altered(*args):
            logits, caches = decode(*args)
            calls.append(1)
            return (-logits if len(calls) == 1 else logits), caches

        self._decode = altered
        try:
            return generate(self, requests)
        finally:
            self._decode = decode

    monkeypatch.setattr(ServeEngine, "generate", broken)


@pytest.mark.parametrize("cell", small.CELLS)
def test_a_token_altered_where_it_is_produced_is_not_correct(cell,
                                                             monkeypatch):
    _alter_first_decoded_token(monkeypatch)
    out = serve_closed.run(small.context(cell))
    assert not out.correct
    gap = out.checks["served_token_gap_mean"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell", small.CELLS)
def test_the_float8_control_reads_far_above_the_program(cell):
    ctx = small.context(cell)
    server = serve_closed.setup(ctx)
    rounds = [serve_closed.one_round(server) for _ in range(3)]
    picked = serve_closed.sample(rounds, ctx.seed, 40)
    args = (server.ref, server.spec, server.key, picked)
    program = serve_closed.served_gaps(*args)
    control = serve_closed.served_gaps(*args, "fp8")
    assert program["tokens"] >= 36
    for name in ("served_token_gap_max", "served_token_gap_mean"):
        assert control[name] > 3 * program[name], (program, control)


def test_the_reference_reads_each_request_from_its_own_prompt():
    from repro.serve.engine import Request

    short = Request(prompt=[5, 6], max_new_tokens=2, out=[7, 8])
    long = Request(prompt=[1, 2, 3, 4], max_new_tokens=3, out=[9, 10, 11])
    rd = serve_closed.Round(0.0, 1.0, 4, [short, long])
    toks, read, served, mask = serve_closed.reference_inputs(
        [(rd, short), (rd, long)])
    assert toks.tolist() == [[5, 6, 7, 0, 0, 0], [1, 2, 3, 4, 9, 10]]
    assert read.tolist() == [[1, 2, 2], [3, 4, 5]]
    assert served.tolist() == [[7, 8, 8], [9, 10, 11]]
    assert mask.tolist() == [[True, True, False], [True, True, True]]


def test_off_a_tpu_a_run_fails_and_prints_no_result():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU found" in out.stderr


class _Device:
    platform, device_kind = "tpu", "TPU v5 lite"


def test_the_result_line_carries_the_cells_metrics_and_ends_with_checks():
    from bench import run

    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = spec["workloads"][0]
    metrics = {m["name"]: 1.5 for m in spec["end_to_end"]}
    out = harness.Outcome(True, 10, 0, metrics,
                          {"served_token_gap_max": {"value": 0.1,
                                                    "limit": 0.5}},
                          123)
    line = run.result_line(spec, cell, {}, {}, out, [_Device()], False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 123}
    assert set(line["metrics"]) == {"output_tokens_per_s", "ttft_p95_ms",
                                    "itl_p95_ms", "setup_s"}
    assert line["metrics"]["setup_s"] == {"value": 1.5, "unit": "s"}
    json.loads(json.dumps(line))
