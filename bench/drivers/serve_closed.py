"""Closed-loop serving through ``ServeEngine``.

The contract this driver reads of the program: ``ServeEngine(cfg, params,
max_batch, max_seq)``, ``ServeEngine.generate(list[Request])``, and that
the engine appends each token to ``Request.out`` once it is on the host.
Each request's ``out`` stamps the host time of every append.

A round hands one request from each client to ``generate`` and waits for
all of them; then every client sends its next one.  Set-up builds the
weights on the device from the seed in one jitted call and runs one round
at each prompt length of the mix, so that the window compiles nothing.  After the window, a sample of the finished requests is
checked against the plain reference (see :func:`check`).
"""

from __future__ import annotations

import gc
import tempfile
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench import devtrace, harness
from bench.traffic import ClosedLoop, seed_sequence


@dataclass
class Round:
    t_hand: float          # host time the requests were handed to generate
    t_back: float          # host time generate returned
    plen: int              # the round's prompt length (one for all)
    requests: list


@dataclass
class Server:
    """The program under test, set up for one cell."""

    ctx: harness.Context
    spec: dict
    ref: object
    key: object
    engine: object
    traffic: ClosedLoop


def make_params(ref, spec, key, cfg):
    """The benchmark's weights in the program's layout, built on the
    device in one jitted call, checked against the program's own tree."""
    from repro.models.model import build_model

    params = jax.jit(lambda k: ref.program_params(
        spec, k, jnp.dtype(cfg.param_dtype)))(key)
    want = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    got = jax.tree.map(lambda t: (t.shape, t.dtype), params)
    exp = jax.tree.map(lambda t: (t.shape, t.dtype), want)
    if got != exp:
        raise ValueError(f"weights do not match the program's layout:\n"
                         f"built {got}\nprogram {exp}")
    return params


def setup(ctx: harness.Context) -> Server:
    from repro.serve.engine import Request, ServeEngine

    spec = ctx.config["config"]
    ref = harness.reference(ctx.config)
    cfg = harness.program_config(ctx.config)
    key = harness.seed_key(ctx.seed)
    t = time.perf_counter()
    params = jax.block_until_ready(make_params(ref, spec, key, cfg))
    harness.log(f"[setup] weights {time.perf_counter() - t:.2f} s")
    eng = ctx.workload["engine"]
    engine = ServeEngine(cfg, params, max_batch=eng["max_batch"],
                         max_seq=eng["max_seq"])
    mix = ctx.workload["traffic"]
    warm = ClosedLoop(mix, spec["vocab_size"], ctx.seed, stream=1)
    for plen in warm.prompt_lengths():
        t = time.perf_counter()
        engine.generate([Request(prompt=p, max_new_tokens=2)
                         for p in warm.prompts_of_length(plen)])
        harness.log(f"[setup] warm-up round at prompt {plen}: "
                    f"{time.perf_counter() - t:.2f} s")
    return Server(ctx, spec, ref, key, engine,
                  ClosedLoop(mix, spec["vocab_size"], ctx.seed))


def one_round(server: Server) -> Round:
    from repro.serve.engine import Request

    with jax.profiler.TraceAnnotation("bench.wave"):
        with jax.profiler.TraceAnnotation("bench.build_requests"):
            reqs = []
            for prompt, n_out in server.traffic.next_round():
                r = Request(prompt=prompt, max_new_tokens=n_out)
                r.out = harness.Stamped()
                reqs.append(r)
        with jax.profiler.TraceAnnotation("bench.generate"):
            t_hand = time.perf_counter()
            server.engine.generate(reqs)
            t_back = time.perf_counter()
    return Round(t_hand, t_back, max(len(r.prompt) for r in reqs), reqs)


def window(server: Server, seconds: float, trace_rounds: int = 0):
    """Rounds until ``seconds`` have passed (the last one runs to its end);
    the first ``trace_rounds`` of them under the profiler.  Returns
    (start, rounds, trace or None)."""
    t0 = time.perf_counter()
    rounds, tr = [], None
    if trace_rounds:
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            with jax.profiler.TraceAnnotation("bench.traced"):
                for _ in range(trace_rounds):
                    rounds.append(one_round(server))
            jax.profiler.stop_trace()
            tr = devtrace.load(tmp)
    while time.perf_counter() < t0 + seconds:
        rounds.append(one_round(server))
    return t0, rounds, tr


def end_to_end(rounds, t0: float, seconds: float) -> dict:
    """The end-to-end metrics over all requests of the window [t0, t0 +
    seconds]: tokens that reached the host in it, the time to first token
    of every request whose first token came in it, and every gap between
    two tokens of one request that both came in it."""
    t1 = t0 + seconds
    n_tok, ttft, itl = 0, [], []
    for rd in rounds:
        for r in rd.requests:
            ts = [t for t in r.out.times if t0 <= t <= t1]
            n_tok += len(ts)
            if r.out.times and t0 <= r.out.times[0] <= t1:
                ttft.append((r.out.times[0] - rd.t_hand) * 1e3)
            itl.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    return {"output_tokens_per_s": n_tok / seconds,
            "ttft_p95_ms": harness.percentile(ttft, 95),
            "itl_p95_ms": harness.percentile(itl, 95)}


def window_shape(rounds, t0: float, seconds: float) -> str:
    """What the window held: requests whose first token came in it, the
    share of it in which a round waited for its first tokens, when no token
    reaches the host and the tokens per second cannot move, the longest
    wait between two tokens of one request, and each round's prompt length
    and seconds (a stall shows in them)."""
    t1 = t0 + seconds
    firsts = [r.out.times[0] for rd in rounds for r in rd.requests
              if r.out.times and t0 <= r.out.times[0] <= t1]
    wait = sum(max(0.0, min(min(r.out.times[0] for r in rd.requests), t1)
                   - max(rd.t_hand, t0))
               for rd in rounds if all(r.out.times for r in rd.requests))
    gap = max((b - a for rd in rounds for r in rd.requests
               for a, b in zip(r.out.times, r.out.times[1:])), default=0.0)
    each = ", ".join(f"{rd.plen}: {rd.t_back - rd.t_hand:.2f} s"
                     for rd in rounds)
    return (f"{len(firsts)} requests got their first token in it; rounds "
            f"waited for first tokens {100 * wait / seconds:.1f}% of it; "
            f"longest gap between two tokens {1e3 * gap:.1f} ms; "
            f"rounds {each}")


# ------------------------------------------------------------ correctness --


def sample(rounds, seed: int, tokens: int):
    """Finished requests drawn from the seed, the longest first, until
    they hold ``tokens`` served tokens: [(round, request)]."""
    done = [(rd, r) for rd in rounds for r in rd.requests if r.out]
    longest = max(range(len(done)),
                  key=lambda i: len(done[i][1].prompt) + len(done[i][1].out))
    rng = np.random.default_rng(seed_sequence(seed, 2))
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    picked, n = [], 0
    for i in order:
        if n >= tokens:
            break
        picked.append(done[i])
        n += len(done[i][1].out)
    return picked


def reference_inputs(picked):
    """Each sampled request's own prompt, then its served tokens but the
    last.  Returns tokens (n, S), read positions (n, k), served tokens (n,
    k) and the mask of real positions (n, k); rows are right-padded, and
    causal attention never reads the padding."""
    seqs, reads, served = [], [], []
    for _, r in picked:
        n = len(r.prompt)
        seqs.append(list(r.prompt) + list(r.out[:-1]))
        reads.append(list(range(n - 1, n - 1 + len(r.out))))
        served.append(list(r.out))
    s = max(map(len, seqs))
    k = max(map(len, reads))
    pad = lambda rows, w, fill: np.array(
        [row + [row[-1] if fill is None else fill] * (w - len(row))
         for row in rows], np.int32)
    mask = np.array([[j < len(row) for j in range(k)] for row in reads])
    return pad(seqs, s, 0), pad(reads, k, None), pad(served, k, None), mask


@jax.jit
def _gaps(ref_logits, tokens):
    """How far the logit of each token lies below the reference's best."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return best - got


def served_gaps(ref, spec, key, picked, mode: str = "f32") -> dict:
    """How far served tokens' logits lie below the reference's best, over
    the sampled requests: the widest gap, the mean gap, and how many tokens
    were compared.  With ``mode`` "fp8" the token judged at each position
    is the one the float8 control puts first (the control's reading)."""
    toks, read, served, mask = reference_inputs(picked)
    want = ref.logits(spec, key, jnp.asarray(toks), jnp.asarray(read))
    if mode != "f32":
        ctl = ref.logits(spec, key, jnp.asarray(toks), jnp.asarray(read),
                         mode)
        served = np.asarray(jnp.argmax(ctl, axis=-1), np.int32)
    gaps = np.asarray(_gaps(want, jnp.asarray(served)))[mask]
    return {"served_token_gap_max": float(gaps.max()),
            "served_token_gap_mean": float(gaps.mean()),
            "tokens": int(mask.sum())}


def check(server: Server, rounds) -> dict:
    """Compare a sample of what the window served with the reference: each
    number the cell's file gives a limit, beside that limit."""
    chk = server.ctx.workload["check"]
    picked = sample(rounds, server.ctx.seed, chk["tokens"])
    t = time.perf_counter()
    read = served_gaps(server.ref, server.spec, server.key, picked)
    harness.log(f"[check] {len(picked)} requests, {read['tokens']} served "
                f"tokens compared in {time.perf_counter() - t:.1f} s: {read}")
    return {name: {"value": read[name], "limit": limit}
            for name, limit in chk["limits"].items()}


# -------------------------------------------------------------------- run --


def run(ctx: harness.Context) -> harness.Outcome:
    server = setup(ctx)
    trace_rounds = ctx.workload["trace_rounds"] if ctx.trace else 0
    counter = harness.CompileCounter()
    counter.on = True
    t0, rounds, tr = window(server, ctx.seconds, trace_rounds)
    counter.on = False
    harness.log(f"[window] {len(rounds)} rounds; {counter.report()}")
    harness.log(f"[window] {window_shape(rounds, t0, ctx.seconds)}")
    metrics = end_to_end(rounds, t0, ctx.seconds)
    metrics["setup_s"] = t0 - ctx.t_start
    started = [r for rd in rounds if rd.t_hand <= t0 + ctx.seconds
               for r in rd.requests]
    failed = sum(len(r.out) != r.max_new_tokens for r in started)
    peak = harness.memory_peak_bytes(ctx.devices) if ctx.devices else 0
    server.engine = None            # free the program's state first
    gc.collect()
    checks = check(server, rounds)
    checks["unfinished_requests"] = {"value": failed, "limit": 0}
    correct = all(bool(np.isfinite(c["value"])) and c["value"] <= c["limit"]
                  for c in checks.values())
    record = {"rounds": rounds, "t0": t0, "seconds": ctx.seconds,
              "trace_rounds": trace_rounds}
    return harness.Outcome(correct, len(started), failed, metrics, checks,
                           peak, record, tr)
