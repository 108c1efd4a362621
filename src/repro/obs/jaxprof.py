"""Profiling hooks: jit retrace counters and peak-RSS sampling.

The repo leans on two compilation invariants that used to be folklore:

* **serving**: padded batch buckets mean the zero-shot sampler compiles
  once per bucket, then replays — a warm request stream causes zero new
  compiles (see ``docs/serving.md``);
* **training**: PPO traces one program per ``(segment, shape)`` config —
  iterations 2..N reuse the programs traced in iteration 1.

This module turns both into *asserted metrics*.  jit call sites register
themselves here (:func:`register`), and :func:`cache_size` reads the
compiled-program count off a jitted callable via its ``_cache_size()``
introspection hook (available on ``jax.jit`` / ``pjit`` wrappers; we
fall back to 0-with-a-shrug when a jax version hides it, never crash).
:class:`RetraceMonitor` snapshots the registry so tests and benchmarks
can pin *deltas* ("0 new compiles across this warm replay") rather than
absolute counts, which module-level jits shared across tests would make
flaky.  :func:`backend_compiles` counts every XLA backend compile in
the process, registered or not.  :func:`export_gauges` mirrors the
counts into a :class:`~repro.obs.metrics.MetricsRegistry` as
``jax_jit_cache_size{fn=...}`` gauges so they ship with every metrics
snapshot.

Peak-RSS sampling lives here too (:func:`peak_rss_bytes`) — it is the
``ru_maxrss`` helper benchmarks have used since PR 1, relocated so every
telemetry consumer shares one definition; ``benchmarks/common`` now
delegates to it.

:func:`enable_compile_cache` is the one place the persistent compilation
cache is configured; every command-line entry point calls it first.
"""
from __future__ import annotations

import os
import resource
import sys
from typing import Any, Callable, Dict, Optional

# <repo>/.cache/jax (gitignored): a fixed path, because the cache key
# includes it — a directory that moves between runs never hits
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".cache", "jax"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is changed here; otherwise the cache lives at
    :data:`REPO_CACHE_DIR`.  Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR

# ---------------------------------------------------------------- registry
# name -> jitted callable.  Keyed by explicit name (module-qualified by
# convention, e.g. "serve.sample_batch") so snapshots read well.
_JITTED: Dict[str, Any] = {}


def register(name: str, fn: Any) -> Any:
    """Register a jitted callable under ``name``; returns ``fn``.

    Call at module import right after the ``jax.jit(...)`` site::

        _my_jit = jaxprof.register("ppo.update", jax.jit(_update_fn, ...))

    Re-registering a name overwrites (modules may be reloaded in tests).
    """
    _JITTED[name] = fn
    return fn


def registered() -> Dict[str, Any]:
    """The live name → jitted-callable registry (do not mutate)."""
    return _JITTED


def cache_size(fn: Any) -> int:
    """Number of compiled programs cached on a jitted callable.

    Uses the ``_cache_size()`` introspection method jax exposes on jit
    wrappers; returns 0 if the hook is missing (old/new jax) — callers
    pin *deltas*, and a constant 0 keeps those assertions vacuous rather
    than wrong.
    """
    probe = getattr(fn, "_cache_size", None)
    if callable(probe):
        try:
            return int(probe())
        except Exception:
            return 0
    return 0


def retrace_counts() -> Dict[str, int]:
    """``{name: compiled-program count}`` for every registered jit."""
    return {name: cache_size(fn) for name, fn in _JITTED.items()}


def total_retraces() -> int:
    """Sum of compiled-program counts across all registered jits."""
    return sum(retrace_counts().values())


class RetraceMonitor:
    """Pin compile-count *deltas* over a code region.

    ::

        mon = RetraceMonitor()            # snapshots at construction
        ... run a warm replay ...
        assert mon.delta() == {}          # no new compiles anywhere

    ``delta()`` only reports names whose count moved (or appeared), so
    the empty dict *is* the "zero new compiles" assertion and failures
    name the offending program.
    """

    def __init__(self) -> None:
        self.baseline = retrace_counts()

    def reset(self) -> None:
        """Re-snapshot; subsequent deltas are relative to now."""
        self.baseline = retrace_counts()

    def delta(self) -> Dict[str, int]:
        """Per-jit compile-count growth since the last snapshot."""
        out: Dict[str, int] = {}
        for name, n in retrace_counts().items():
            d = n - self.baseline.get(name, 0)
            if d:
                out[name] = d
        return out

    def total_delta(self) -> int:
        return sum(self.delta().values())


# --------------------------------------------------------- backend compiles
# Registered jits only see their own caches; the segmented paths compile
# per-segment programs and eager ops the registry never hears of.  XLA
# reports every backend compile through jax.monitoring, so one listener,
# registered on first use, counts them all for the whole process.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = 0
_listening = False


def _on_duration_event(event: str, duration: float, **kwargs: Any) -> None:
    global _compiles
    if event == BACKEND_COMPILE_EVENT:
        _compiles += 1


def backend_compiles() -> int:
    """XLA backend compiles in this process (every program, eager ops and
    per-segment programs included) since the first call, which registers
    the ``jax.monitoring`` listener; diff two readings for a region."""
    global _listening
    if not _listening:
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        _listening = True
    return _compiles


def export_gauges(registry) -> Dict[str, int]:
    """Mirror retrace counts into ``registry`` as gauges.

    Sets ``jax_jit_cache_size{fn=<name>}`` for every registered jit and
    returns the counts dict.  ``registry`` is a
    :class:`~repro.obs.metrics.MetricsRegistry`.
    """
    counts = retrace_counts()
    g = registry.gauge("jax_jit_cache_size",
                       "compiled programs cached per registered jit",
                       ("fn",))
    for name, n in counts.items():
        g.set(n, fn=name)
    return counts


# ---------------------------------------------------------------- peak RSS
def peak_rss_bytes() -> int:
    """Peak resident set size of this process, in bytes.

    ``ru_maxrss`` is KiB on Linux and bytes on macOS; normalize to
    bytes.  This is the lifetime high-water mark — sample before/after a
    section and diff if you want attribution.
    """
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    if sys.platform != "darwin":
        peak *= 1024
    return int(peak)


def export_rss_gauge(registry) -> int:
    """Set ``process_peak_rss_bytes`` on ``registry``; returns bytes."""
    rss = peak_rss_bytes()
    registry.gauge("process_peak_rss_bytes",
                   "lifetime peak resident set size").set(rss)
    return rss
