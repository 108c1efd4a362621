"""Observability package: metrics registry, span tracer, profiling hooks.

These pin the contracts the rest of the repo leans on: ``CounterDict``
keeps the legacy dict API bit-for-bit (ints stay ints), histogram
percentiles agree exactly with ``np.percentile`` over the same samples,
the tracer stamps simulated time when given a ``SimulatedClock``-style
object, and the Chrome export is valid trace-event JSON.
"""
import json
import math

import jax
import numpy as np
import pytest

from repro.obs import jaxprof
from repro.obs.metrics import (Counter, CounterDict, Gauge, Histogram,
                               MetricsRegistry, RunLog, counters_flat,
                               merge_snapshots, read_jsonl)
from repro.obs.trace import Tracer, get_tracer, set_tracer


# ----------------------------------------------------------------- metrics
def test_counter_labels_and_int_preservation():
    c = Counter("events_total", label_names=("event",))
    c.inc(event="hit")
    c.inc(3, event="miss")
    assert c.get(event="hit") == 1 and isinstance(c.get(event="hit"), int)
    assert c.get(event="miss") == 3
    assert c.get(event="never") == 0
    assert c.total() == 4 and isinstance(c.total(), int)


def test_counterdict_is_a_drop_in_dict():
    """The adapter keeps every call-site idiom the hand-rolled dicts used:
    ``counts[k] += 1``, ``dict(counts)``, ``k in counts``, iteration."""
    c = Counter("events_total", label_names=("event",))
    d = CounterDict(c, initial=("cache", "disk"))
    assert dict(d) == {"cache": 0, "disk": 0}
    d["cache"] += 2
    d["new_key"] += 1                     # unseen keys start at 0
    assert d["cache"] == 2 and d["new_key"] == 1
    assert isinstance(d["cache"], int)
    assert "cache" in d and "nope" not in d
    assert set(d) >= {"cache", "disk", "new_key"}
    assert len(d) == 3
    # writes land in the underlying counter (single source of truth)
    assert c.get(event="cache") == 2


def test_histogram_percentile_matches_numpy_exactly():
    rng = np.random.RandomState(0)
    xs = rng.exponential(0.05, size=257)
    h = Histogram("latency_seconds", label_names=("source",))
    for x in xs:
        h.observe(float(x), source="cache")
    for q in (50, 90, 99):
        assert h.percentile(q, labels={"source": "cache"}) == \
            pytest.approx(float(np.percentile(xs, q)), abs=0, rel=0)
    assert h.count(labels={"source": "cache"}) == len(xs)
    assert h.mean(labels={"source": "cache"}) == pytest.approx(xs.mean())


def test_histogram_merged_percentile_across_series():
    h = Histogram("lat", label_names=("source",))
    a, b = [0.1, 0.2, 0.3], [1.0, 2.0]
    for x in a:
        h.observe(x, source="a")
    for x in b:
        h.observe(x, source="b")
    assert h.percentile(50) == pytest.approx(float(np.percentile(a + b, 50)))
    assert h.count() == 5


def test_registry_snapshot_and_prometheus_text():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests", ("route",))
    c.inc(route="/place")
    reg.gauge("queue_depth", "queue").set(4)
    reg.histogram("lat_seconds", "latency").observe(0.5)
    snap = reg.snapshot()
    assert snap["requests_total"]["type"] == "counter"
    assert snap["queue_depth"]["values"][""] == 4
    assert snap["lat_seconds"]["values"][""]["count"] == 1
    json.dumps(snap)                      # snapshot is strict-JSON-able
    text = reg.to_prometheus()
    assert '# TYPE requests_total counter' in text
    assert 'requests_total{route="/place"} 1' in text
    assert "# TYPE lat_seconds histogram" in text
    # get-or-create returns the same object; schema mismatch raises
    assert reg.counter("requests_total", "requests", ("route",)) is c
    with pytest.raises(ValueError):
        reg.counter("requests_total", "requests", ("other",))


def test_merge_snapshots_sums_counters_and_histograms():
    def one():
        reg = MetricsRegistry()
        reg.counter("n_total", "", ("k",)).inc(2, k="x")
        reg.histogram("lat", "").observe(0.25)
        reg.gauge("depth", "").set(7)
        return reg.snapshot()

    merged = merge_snapshots([one(), one()])
    flat = counters_flat(merged)
    assert flat['n_total{k="x"}'] == 4
    assert merged["lat"]["values"][""]["count"] == 2
    assert merged["lat"]["values"][""]["sum"] == pytest.approx(0.5)
    assert flat["depth"] == 7             # gauges: last write wins, not sum


def test_runlog_round_trip_and_nan_handling(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    log = RunLog(path, run="t")
    log.emit({"iter": 0, "reward": 1.5})
    log.emit({"iter": 1, "reward": float("nan"), "best": float("inf")})
    log.close()
    recs = read_jsonl(path)
    assert [r["seq"] for r in recs] == [0, 1]
    assert all(r["run"] == "t" for r in recs)
    assert recs[1]["reward"] is None and recs[1]["best"] is None
    # every line is strict JSON (json.loads would have raised otherwise)
    assert recs[0]["reward"] == 1.5


# ------------------------------------------------------------------ tracer
class _FakeClock:
    """SimulatedClock-alike: ``now()`` in simulated seconds."""

    def __init__(self, t=100.0):
        self.t = t

    def now(self):
        return self.t


def test_tracer_uses_simulated_clock_when_given():
    clock = _FakeClock(100.0)
    tr = Tracer(enabled=True)
    with tr.span("svc.work", cat="serve", clock=clock, key="g1") as sp:
        clock.t = 102.5
        sp.set(extra=1)
    (span,) = tr.spans
    assert span.ts == pytest.approx(100.0)
    assert span.dur == pytest.approx(2.5)
    assert span.args == {"key": "g1", "extra": 1}


def test_tracer_wall_clock_and_chrome_export(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("outer", cat="test"):
        with tr.span("inner", cat="test", tid=3):
            math.sqrt(2.0)
    path = str(tmp_path / "trace.json")
    tr.export_chrome(path)
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 2
    by_name = {e["name"]: e for e in evs}
    for e in evs:
        assert e["ph"] == "X" and e["cat"] == "test"
        assert isinstance(e["ts"], float) and e["dur"] >= 0
    assert by_name["inner"]["tid"] == 3
    # inner nests inside outer on the timeline (microseconds)
    assert by_name["outer"]["ts"] <= by_name["inner"]["ts"]
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"]


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("x") as sp:
        sp.set(k=1)                       # no-op, must not raise
    assert tr.spans == []
    assert tr.to_chrome()["traceEvents"] == []


def test_set_tracer_returns_previous():
    mine = Tracer(enabled=True)
    old = set_tracer(mine)
    try:
        assert get_tracer() is mine
        with get_tracer().span("via-default"):
            pass
        assert [s.name for s in mine.spans] == ["via-default"]
    finally:
        set_tracer(old)
    assert get_tracer() is old


# ----------------------------------------------------------------- jaxprof
def test_cache_size_counts_one_compile_per_shape():
    f = jax.jit(lambda x: x + 1)
    assert jaxprof.cache_size(f) == 0
    f(np.ones(3, np.float32))
    f(np.ones(3, np.float32))             # warm: same shape, no retrace
    assert jaxprof.cache_size(f) == 1
    f(np.ones(5, np.float32))             # new shape: one more program
    assert jaxprof.cache_size(f) == 2
    assert jaxprof.cache_size(object()) == 0   # non-jit: 0, never raises


def test_retrace_monitor_reports_deltas_only():
    f = jax.jit(lambda x: x * 2)
    jaxprof.register("test.tmp_fn", f)
    try:
        mon = jaxprof.RetraceMonitor()
        assert mon.delta() == {} and mon.total_delta() == 0
        f(np.ones(2, np.float32))
        assert mon.delta() == {"test.tmp_fn": 1}
        assert mon.total_delta() == 1
        mon.reset()
        assert mon.delta() == {}
        reg = MetricsRegistry()
        jaxprof.export_gauges(reg)
        flat = counters_flat(reg.snapshot())
        assert flat['jax_jit_cache_size{fn="test.tmp_fn"}'] == 1
    finally:
        del jaxprof._JITTED["test.tmp_fn"]


def test_peak_rss_gauge_is_positive():
    assert jaxprof.peak_rss_bytes() > 0
    reg = MetricsRegistry()
    jaxprof.export_rss_gauge(reg)
    assert counters_flat(reg.snapshot())["process_peak_rss_bytes"] > 0


def test_backend_compiles_count_every_program_once():
    c0 = jaxprof.backend_compiles()
    f = jax.jit(lambda x: x * 3 - 1)          # registered nowhere
    f(np.ones(7, np.float32))
    c1 = jaxprof.backend_compiles()
    assert c1 >= c0 + 1
    f(np.ones(7, np.float32))                 # warm: nothing compiles
    assert jaxprof.backend_compiles() == c1
    f(np.ones(9, np.float32))                 # new shape: one more
    assert jaxprof.backend_compiles() == c1 + 1


def test_spans_share_the_profiler_clock():
    """A served batch's device program, moved onto the host clock by the
    benchmark's trace reduction (through its window annotation), lies
    inside the ``serve.batch`` span that dispatched and awaited it."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench import trace as ctrace
    from repro.core.policy import PolicyConfig
    from repro.core.ppo import PPOConfig, PPOTrainer
    from repro.graphs import synthetic as S
    from repro.serve import PlacementService, ServeConfig, WallClock
    from repro.sim import p100_topology

    trainer = PPOTrainer(PolicyConfig(hidden=32, gnn_layers=2,
                                      placer_layers=1, ffn=64, window=32,
                                      max_devices=8),
                         PPOConfig(num_samples=2, epochs=1), seed=0)
    svc = PlacementService(trainer, ServeConfig(max_batch=1, num_samples=2,
                                                finetune_iters=0),
                           clock=WallClock())
    topo = p100_topology(4)
    svc.submit(S.rnnlm(2, time_steps=3), topo)        # compiles the bucket
    mine = Tracer()
    old = set_tracer(mine)
    try:
        with ctrace.Capture() as cap:                 # a full batch flushes
            req = svc.submit(S.rnnlm(2, time_steps=4), topo)
    finally:
        set_tracer(old)
    assert req.source == "zero_shot"
    lo = [s for s, _, n in cap.raw["host"] if n == ctrace.WINDOW][0]
    shift = lo - cap.clock_start
    batch = [s for s in mine.spans if s.name == "serve.batch"]
    assert len(batch) == 1
    b0, b1 = batch[0].ts + shift, batch[0].ts + batch[0].dur + shift
    progs = [(s, e) for ivs in cap.raw["devices"].values()
             for s, e, n in ivs if "sample_batch" in n]
    assert progs
    for s, e in progs:
        assert b0 - 1e-3 <= s and e <= b1 + 1e-3, (s - b0, e - b1)
