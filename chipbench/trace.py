"""Reduction of a JAX profiler trace to device busy time, device time by
layer, the longest device programs and the host's doing in idle gaps.

A capture records one traced window between two host annotations that
the driver puts around the work it drives.  Device intervals come from
the ``XLA Modules`` line of each ``/device:TPU:<k>`` plane (one event per
program run); where a trace has no TPU plane, as on the CPU, from the
host-thread events that carry an ``hlo_module`` stat.  The program's own
``obs`` spans (host clock) are put on the profiler clock through the
window annotation, whose start is known on both clocks.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]      # start s, end s, name

WINDOW = "chipbench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """A program's name without the trace's ``(<run id>)`` suffix."""
    return _SUFFIX.sub("", name)


def read(pd) -> Dict[str, object]:
    """(device intervals per device, host annotations) of a ProfileData."""
    devices: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    devices[plane.name] = [
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                         * 1e-9, module_name(e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                          * 1e-9, e.name)
                    host.append(iv)
    if not devices:                      # CPU backend: ops on host threads
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    mod = dict(e.stats).get("hlo_module")
                    if mod:
                        devices.setdefault("/host:CPU", []).append(
                            (e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9, str(mod)))
    return {"devices": devices, "host": host}


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged [start, end) covered by the intervals, clipped to [lo, hi)."""
    spans = sorted((max(s, lo), min(e, hi)) for s, e, _ in intervals
                   if e > lo and s < hi)
    out: List[Tuple[float, float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        elif e > s:
            out.append((s, e))
    return out


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle stretches of [lo, hi) between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def layer_of(name: str, layers: Dict[str, List[str]]) -> str:
    """The first layer whose patterns match the program name."""
    for layer, pats in layers.items():
        if any(re.search(p, name) for p in pats):
            return layer
    return "other"


def by_layer(intervals: Sequence[Interval], layers: Dict[str, List[str]],
             lo: float, hi: float) -> Dict[str, float]:
    """Device seconds per layer within [lo, hi)."""
    out: Dict[str, float] = {}
    for s, e, name in intervals:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = layer_of(name, layers)
            out[key] = out.get(key, 0.0) + d
    return out


def top_programs(intervals: Sequence[Interval], lo: float, hi: float,
                 k: int = 10) -> List[List[object]]:
    """The ``k`` programs that took the most device time in [lo, hi)."""
    tot: Dict[str, float] = {}
    for s, e, name in intervals:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            tot[name] = tot.get(name, 0.0) + d
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def attribute(idle: List[Tuple[float, float]], spans: Sequence[Interval],
              k: int = 10) -> List[List[object]]:
    """Idle seconds by the innermost host span open at each gap's middle
    (``host`` where none is), the ``k`` largest.  Spans nest or follow
    one another, so the innermost open span is the latest-starting one
    that has not ended."""
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]
    tot: Dict[str, float] = {}
    for s, e in idle:
        mid = 0.5 * (s + e)
        name = "host"
        for j in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if spans[j][1] > mid:
                name = spans[j][2]
                break
        tot[name] = tot.get(name, 0.0) + (e - s)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def reduce(raw: Dict[str, object], layers: Dict[str, List[str]],
           spans: Sequence[Interval] = (), clock_start: float = 0.0
           ) -> Dict[str, object]:
    """Busy and window seconds (averaged over the devices), device seconds
    per layer, the top programs and the idle gaps by host span.

    ``spans`` are the program's spans on the host clock; ``clock_start``
    is the host-clock time at which the window annotation opened."""
    windows = [h for h in raw["host"] if h[2] == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    lo, hi = windows[0][0], windows[0][1]
    shift = lo - clock_start
    host_spans = [(s + shift, e + shift, n) for s, e, n in spans] + [
        h for h in raw["host"]
        if h[2].startswith("chipbench.") and h[2] != WINDOW]
    devs = raw["devices"]
    if not devs:
        raise ValueError("trace holds no device programs")
    busy_s, per_layer, top, idle = [], {}, [], []
    for i, (name, ivs) in enumerate(sorted(devs.items())):
        merged = union(ivs, lo, hi)
        busy_s.append(sum(e - s for s, e in merged))
        for k, v in by_layer(ivs, layers, lo, hi).items():
            per_layer[k] = per_layer.get(k, 0.0) + v / len(devs)
        if i == 0:
            top = top_programs(ivs, lo, hi)
            idle = attribute(gaps(merged, lo, hi), host_spans)
    return {"busy_s": sum(busy_s) / len(busy_s), "window_s": hi - lo,
            "layer_s": per_layer, "device_ops": top, "idle_gaps": idle}


def merge(reds: Sequence[Dict[str, object]], k: int = 10
          ) -> Dict[str, object]:
    """One reduction of several captures that together cover a window
    (its phases, traced one after another): seconds add up, and the top
    programs and idle gaps are ranked over all of them."""
    def ranked(key):
        tot: Dict[str, float] = {}
        for r in reds:
            for n, t in r[key]:
                tot[n] = tot.get(n, 0.0) + t
        return [[n, t] for n, t in sorted(tot.items(),
                                          key=lambda x: -x[1])[:k]]
    layer_s: Dict[str, float] = {}
    for r in reds:
        for n, t in r["layer_s"].items():
            layer_s[n] = layer_s.get(n, 0.0) + t
    return {"busy_s": sum(r["busy_s"] for r in reds),
            "window_s": sum(r["window_s"] for r in reds),
            "layer_s": layer_s, "device_ops": ranked("device_ops"),
            "idle_gaps": ranked("idle_gaps")}


class Capture:
    """Profiler capture of one window into a scratch directory under
    ``$TMPDIR``; ``raw`` holds the trace's intervals afterwards and the
    directory is removed."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.raw: Optional[Dict[str, object]] = None
        self.clock_start = 0.0
        self._ann = None

    def __enter__(self) -> "Capture":
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1          # annotations, not every TraceMe
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self.clock_start = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        import jax
        self._ann.__exit__(*exc)
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                from jax.profiler import ProfileData
                path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                 recursive=True)[0]
                self.raw = read(ProfileData.from_file(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
