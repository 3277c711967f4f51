"""What every driver and reader of the benchmark shares: the files found by
name, the program's configuration built from a configuration file, the
seed's keys, the chip check, the count of compilations, token times, and
the statistics of the end-to-end metrics."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
import time
from dataclasses import dataclass
from typing import Any

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE = ROOT / ".jax_cache" / "bench"


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def enable_cache() -> str:
    """Keep JAX's compilation cache in ``.jax_cache/bench/`` in the
    checkout, whatever the environment named, and cache every program
    however quick its compile; call before JAX is imported.  The directory
    is the benchmark's alone: entries that other writers leave beside
    JAX's own bookkeeping files make every write fail."""
    import os

    inherited = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    from repro import compile_cache

    import jax

    compile_cache.enable()
    # also where something imported JAX before the environment was set
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    cache = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"[cache] {cache} (the environment named: {inherited})")
    return cache


def load_json(*parts) -> dict:
    return json.loads(BENCH.joinpath(*parts).read_text())


def load_metric(name: str):
    """The reader ``bench/metrics/<name>.py`` (a name may hold dots)."""
    mod_name = "bench.metrics." + name.replace(".", "__")
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            mod_name, BENCH / "metrics" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[mod_name] = mod
    return sys.modules[mod_name]


def reference(config: dict):
    """The plain reference of a configuration's architecture."""
    return importlib.import_module(f"bench.reference.{config['architecture']}")


def program_config(config: dict):
    """The program's ``ModelConfig``: the registry entry, with the sizes of
    the configuration file and its kernel schedules installed the way users
    install them (``launch.serve.apply_tuned_schedules``)."""
    from repro.configs.base import get_config
    from repro.launch.serve import apply_tuned_schedules

    base = get_config(config["registry"])
    fields = reference(config).program_config(config["config"])
    fields.update(config.get("program", {}))
    changed = {k: (getattr(base, k), v) for k, v in fields.items()
               if getattr(base, k) != v}
    if changed:
        log(f"[config] {config['registry']}: registry -> file: {changed}")
    cfg = dataclasses.replace(base, **fields)
    if config.get("schedules"):
        cfg, installed = apply_tuned_schedules(
            cfg, str(BENCH / "configs" / config["schedules"]))
        log(f"[config] schedules installed: {installed}")
    return cfg


def seed_key(seed: int, stream: int = 0):
    """A JAX key from any whole number: the seed is hashed, not cut to 32
    bits."""
    import jax
    import numpy as np

    from bench.traffic import seed_sequence

    words = seed_sequence(seed, 0, stream).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def check_device(chips: int):
    """The devices of a run; exits non-zero, printing no result, when JAX
    finds no TPU or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    d = devices[0]
    log(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    if d.platform != "tpu":
        log(f"[device] no TPU found (JAX's first device is {d.platform!r})")
        sys.exit(3)
    if len(devices) < chips:
        log(f"[device] the cell needs {chips} TPUs, found {len(devices)}")
        sys.exit(3)
    return devices[:chips]


class CompileCounter:
    """Counts, while it is on, the programs JAX lowers, and of those it
    hands to the compiler the ones the persistent cache held (``hits``) and
    the ones compiled anew (``misses``)."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.lowered = self.hits = self.misses = 0
        self.on = False
        names = {dispatch.JAXPR_TO_MLIR_MODULE_EVENT: "lowered",
                 "/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}

        def listen(event, *_a, **_kw):
            if self.on and event in names:
                setattr(self, names[event], getattr(self, names[event]) + 1)

        jax.monitoring.register_event_duration_secs_listener(listen)
        jax.monitoring.register_event_listener(listen)

    def report(self) -> str:
        return (f"programs lowered {self.lowered}, found in the compile cache "
                f"{self.hits}, compiled {self.misses} (should be 0)")


class Stamped(list):
    """A request's output list that stamps the host time of each token as
    the engine appends it."""

    def __init__(self):
        super().__init__()
        self.times: list[float] = []

    def append(self, tok) -> None:
        self.times.append(time.perf_counter())
        super().append(tok)


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between order statistics."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


@dataclass
class Context:
    """What a driver is given."""

    cell: dict
    workload: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: Any = None          # None: no chip (a CPU rehearsal)


@dataclass
class Outcome:
    """What a driver returns."""

    correct: bool
    attempted: int
    failed: int
    metrics: dict                # end-to-end metric name -> value
    checks: dict                 # compared number -> {"value", "limit"}
    memory_peak_bytes: int = 0
    record: Any = None           # the run's record, for the readers
    trace: Any = None            # devtrace.Trace of a traced run
