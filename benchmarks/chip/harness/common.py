"""Paths, the registry of named files, seeds, the device and the result line."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]      # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                          # the checkout
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a missing file)."""


def setup_paths() -> None:
    """The program under test (``src/``), its examples, and this harness."""
    for p in (str(BENCH_DIR), str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    return json.loads(path.read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def config_file(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise BenchError(f"no config {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` beside the harness, imported by path (names may
    hold dots)."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, cell_name: str, trace: bool) -> list:
    """The metric entries this cell reports: end-to-end ones untraced,
    per-layer ones traced.  An entry without ``workloads`` is reported
    wherever its (or, per layer, its ``moves``) end-to-end metric is."""
    e2e = bench["end_to_end"]

    def in_cell(m) -> bool:
        return "workloads" not in m or cell_name in m["workloads"]

    if not trace:
        return [m for m in e2e if in_cell(m)]
    reported = {m["name"] for m in e2e if in_cell(m)}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def seed_words(seed: int, n: int = 2) -> list:
    """``n`` 32-bit words from any non-negative seed (seeds may exceed 32
    bits): numpy's SeedSequence mixes every bit of it."""
    import numpy as np
    return [int(w) for w in
            np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)]


def jax_key(seed: int, salt: int = 0):
    import jax
    w = seed_words(seed + salt * 0x9E3779B1)
    return jax.random.fold_in(jax.random.PRNGKey(w[0]), w[1])


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, caching every program so a second run compiles nothing."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCount:
    """JAX's program builds in this process: how many the persistent cache
    served, how many were compiled, and the seconds spent in both."""

    def __init__(self):
        import jax.monitoring as mon
        self.hits = self.misses = 0
        self.seconds = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def snapshot(self) -> tuple:
        return self.hits, self.misses, self.seconds

    def since(self, snap: tuple) -> str:
        h, m, s = snap
        return (f"{self.hits - h} programs from the cache, "
                f"{self.misses - m} compiled, "
                f"{self.seconds - s:.1f} s building them")


def device_info(chips: int) -> dict:
    """The devices this run uses; raises unless they are TPUs, at least
    ``chips`` of them."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {d.platform} "
                         f"({d.device_kind}); this benchmark runs only on "
                         f"the chip")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


def peaks(kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


class Clock:
    """The run's host clock: seconds from the process's start."""

    def __init__(self, t0: float | None = None):
        self.t0 = time.perf_counter() if t0 is None else t0

    def __call__(self) -> float:
        return time.perf_counter()

    def since_start(self) -> float:
        return time.perf_counter() - self.t0


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)
