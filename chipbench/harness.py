"""What every cell of the benchmark shares: finding its files by name, the
chip check, the compile cache, seeds, compile counting, the per-layer
metric readers and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is ``chipbench/configs/<config>.json``, its traffic mix
``chipbench/traffic/<traffic>.json``; the mix names the driver
(``chipbench/drivers/<driver>.py``) that runs it, and each per-layer
metric is read by ``chipbench/metrics/<metric>.py``.  Adding a cell, a
configuration, a mix or a metric is adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from typing import Any, Dict, List

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"chipbench: no workload named {name!r} in "
                     f"BENCHMARK.json")


def config_file(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(ROOT, c["file"]))
    raise SystemExit(f"chipbench: no configuration named {name!r}")


def traffic_file(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sub_seeds(seed: int, k: int) -> List[int]:
    """``k`` independent non-negative 31-bit seeds derived from ``seed``
    (any whole number; the driver's are larger than 32 bits hold)."""
    ss = np.random.SeedSequence(abs(int(seed)) * 2 + (seed < 0))
    return [int(x) & 0x7FFFFFFF for x in ss.generate_state(k)]


def require_chips(n: int) -> Any:
    """The first device, after checking that JAX sees at least ``n`` TPU
    chips; exits non-zero otherwise.  There is no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise SystemExit(
            f"chipbench: needs {n} TPU chip(s); JAX found "
            f"{len(devs)} device(s) of platform={devs[0].platform!r} "
            f"({devs[0].device_kind})")
    return devs[0]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or, where that is unset, at the fixed ``<checkout>/.cache/jax``; every
    program is cached, however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA backend compiles (every program, eager ops included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs) -> None:
        if event == self.EVENT:
            self.n += 1


def device_sync() -> None:
    """Wait until every program already dispatched to the device has run
    (a chip runs its programs in order; this one is compiled once)."""
    import jax
    import jax.numpy as jnp
    jax.jit(jnp.zeros, static_argnums=0)(()).block_until_ready()


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
             for d in devices]
    return max(peaks)


def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile (linear interpolation) of all values; a
    request that failed is +inf, so it misses any limit, and a percentile
    that reaches into the failures is +inf."""
    big = 1e300
    v = np.minimum(np.asarray(values, np.float64), big)
    p = float(np.percentile(v, q))
    return float("inf") if p >= big / 2 else p


class Check:
    """One number compared with its limit; ``ok`` when value <= limit."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def per_layer(bench: Dict[str, Any], wl: Dict[str, Any], reported: List[str],
              inputs: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Per-layer metrics of this cell, each from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and wl["name"] not in cells:
            continue
        if cells is None and m["moves"] not in reported:
            continue
        value = load_module("metrics", m["name"]).read(inputs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end(bench: Dict[str, Any], wl: Dict[str, Any]) -> List[Dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"]
            if wl["name"] in m.get("workloads", [wl["name"]])]


def emit(result: Dict[str, Any], checks: List[Check]) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output
    (the numbers again, under ``checks``, as its last key)."""
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    result = dict(result, checks={c.name: {"value": c.value,
                                           "limit": c.limit}
                                  for c in checks})
    print(json.dumps(result), flush=True)


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)

