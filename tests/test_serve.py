"""Placement service: cache semantics, micro-batching, escalation ladder.

The integration test drives the full ladder under a simulated clock, so
latency/hit-rate assertions are exact functions of the request trace.
The contention-mode tests pin the provenance rule: the topology digest
carries the simulator mode, and a mode flip over a warm store re-infers
with ``stale_served == 0`` — exactly like a policy bump.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.featurize import bucket_size, featurize
from repro.core.graph import topo_relabel
from repro.core.policy import PolicyConfig
from repro.core.ppo import PPOConfig, PPOTrainer
from repro.graphs import synthetic as S
from repro.serve import (MicroBatcher, PlacementService, PlacementCache,
                         PersistentStore, ServeConfig, SimulatedClock,
                         policy_hash, topology_fingerprint)
from repro.serve.cache import CacheEntry
from repro.sim.device import p100_topology
from repro.sim.reference import simulate_ref


def _entry(mk, pl_len=4):
    return CacheEntry(np.zeros(pl_len, np.int32), mk, mk)


# ------------------------------------------------------------------- cache
def test_cache_lru_eviction_and_stats():
    c = PlacementCache(capacity=2, policy="lru")
    c.put(("a", "t"), _entry(1.0))
    c.put(("b", "t"), _entry(2.0))
    assert c.get(("a", "t")) is not None      # refresh a
    c.put(("c", "t"), _entry(3.0))            # evicts b (LRU)
    assert c.get(("b", "t")) is None
    assert c.get(("c", "t")) is not None
    assert c.stats.evictions == 1
    assert c.stats.hits == 2 and c.stats.misses == 1
    assert c.stats.hit_rate == pytest.approx(2 / 3)


def test_cache_lfu_prefers_hot_entries():
    c = PlacementCache(capacity=2, policy="lfu")
    c.put(("hot", "t"), _entry(1.0))
    for _ in range(5):
        assert c.get(("hot", "t")) is not None
    c.put(("cold", "t"), _entry(2.0))
    c.put(("new", "t"), _entry(3.0))          # evicts cold (0 hits), not hot
    assert c.peek(("hot", "t")) is not None
    assert c.peek(("cold", "t")) is None


def test_cache_publish_is_monotone():
    c = PlacementCache(capacity=4)
    key = ("g", "t")
    assert c.publish(key, np.zeros(4, np.int32), 2.0, source="zero_shot")
    assert not c.publish(key, np.ones(4, np.int32), 2.5)   # regression refused
    assert c.peek(key).measured_makespan == 2.0
    assert c.publish(key, np.ones(4, np.int32), 1.5, source="finetuned")
    e = c.peek(key)
    assert e.measured_makespan == 1.5 and e.source == "finetuned"
    assert np.all(e.placement == 1)


# ----------------------------------------------------------------- batcher
def _gb(g, topo):
    return featurize(g, max_deg=8, topo=topo)


def test_batcher_flushes_full_groups_and_backfills():
    topo = p100_topology(4)
    g = S.rnnlm(2, time_steps=3)
    mb = MicroBatcher(max_batch=3, max_wait_s=1.0)
    key = MicroBatcher.group_key("tfp", 4, g.num_nodes)
    for i in range(4):
        mb.add(key, f"r{i}", _gb(g, topo), now=0.0)
    flushes = mb.ready(now=0.0)
    assert len(flushes) == 1 and flushes[0].real == 3      # full batch only
    assert len(mb) == 1
    fl = mb.ready(now=2.0)[0]                              # timeout flush
    assert fl.real == 1
    # batch dim always padded to max_batch; node dim to the bucket
    assert fl.sgb.op.shape == (3, bucket_size(g.num_nodes))
    assert fl.sgb.nbr_idx.shape[2] == 16                   # pinned 2*max_deg
    assert len(mb) == 0


def test_batcher_groups_by_compiled_shape():
    topo = p100_topology(4)
    small, big = S.rnnlm(2, time_steps=3), S.rnnlm(2, time_steps=8)
    assert bucket_size(small.num_nodes) != bucket_size(big.num_nodes)
    mb = MicroBatcher(max_batch=4, max_wait_s=0.0)
    for g in (small, big):
        mb.add(MicroBatcher.group_key("tfp", 4, g.num_nodes), g.name,
               _gb(g, topo), now=0.0)
    flushes = mb.ready(now=0.0)
    assert len(flushes) == 2                               # one per bucket
    assert {f.sgb.op.shape[1] for f in flushes} == \
        {bucket_size(small.num_nodes), bucket_size(big.num_nodes)}


# ---------------------------------------------------- escalation ladder
def _relabeled(g, seed):
    rng = np.random.RandomState(seed)
    perm = rng.permutation(g.num_nodes)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(g.num_nodes)
    return topo_relabel(g.name + "-rl", g.op_type[perm], g.flops[perm],
                        g.out_bytes[perm], g.mem_bytes[perm],
                        g.out_shape[perm], inv[g.src], inv[g.dst])


def test_escalation_ladder_under_simulated_clock():
    """Zipf-skewed stream: steady-state hit rate is exact, latencies follow
    the deterministic cost model, and fine-tune escalation strictly
    improves the cached makespan it republishes."""
    pcfg = PolicyConfig(hidden=32, gnn_layers=2, placer_layers=1, ffn=64,
                        window=32, max_devices=8)
    ppo = PPOConfig(num_samples=8, epochs=1)
    trainer = PPOTrainer(pcfg, ppo, seed=0)
    cfg = ServeConfig(max_batch=1, num_samples=2, simulated=True,
                      finetune_iters=6, escalate_margin=0.0, seed=0)
    clock = SimulatedClock()
    svc = PlacementService(trainer, cfg, clock)

    g_hot = S.rnnlm(2, time_steps=3)
    g_cold = topo_relabel("rnnlm-scaled", g_hot.op_type, g_hot.flops * 1.5,
                          g_hot.out_bytes, g_hot.mem_bytes, g_hot.out_shape,
                          g_hot.src, g_hot.dst)
    topo = p100_topology(4).tightened(g_hot.total_mem())

    # zipf-ish two-key stream: hot key (incl. relabelings) dominates
    trace = [g_hot, g_cold, _relabeled(g_hot, 1), g_hot, _relabeled(g_hot, 2),
             g_cold, g_hot, _relabeled(g_hot, 3), g_hot, g_cold,
             _relabeled(g_hot, 4), g_hot]
    reqs = []
    zs_after_first = {}
    for i, g in enumerate(trace):
        r = svc.submit(g, topo, arrival_t=i * 1.0)
        reqs.append(r)
        if r.key not in zs_after_first and svc.cache.peek(r.key) is not None:
            zs_after_first[r.key] = \
                svc.cache.peek(r.key).measured_makespan
        svc.step()      # async worker turn: lets fine-tunes land mid-trace
    svc.drain()

    # ---- steady-state hit rate: exactly 2 misses (one per unique key)
    stats = svc.stats()
    assert stats["misses"] == 2
    assert stats["hit_rate"] == pytest.approx((len(trace) - 2) / len(trace))
    second_half = reqs[len(reqs) // 2:]
    assert all(r.source == "cache" for r in second_half)

    # ---- deterministic latencies from the service-time model
    c = cfg.costs
    for r in reqs:
        if r.source == "cache":
            assert r.latency == pytest.approx(c.lookup_s)
        else:
            assert r.latency == pytest.approx(
                c.lookup_s + c.batch_base_s + c.batch_per_graph_s)

    # ---- every response is a feasible placement of the right arity
    for r in reqs:
        assert np.isfinite(r.makespan)
        assert r.placement.shape == (r.graph.num_nodes,)
        assert r.placement.min() >= 0 and r.placement.max() < 4

    # ---- escalation ran and only ever improved the cached entries
    assert svc.counts["finetunes"] >= 1
    assert svc.counts["finetune_published"] >= 1
    improved = 0
    for key, zs_mk in zs_after_first.items():
        entry = svc.cache.peek(key)
        assert entry.measured_makespan <= zs_mk + 1e-12
        if entry.source == "finetuned":
            assert entry.measured_makespan < zs_mk   # strict improvement
            improved += 1
    assert improved >= 1
    # cache hits after the publish serve the fine-tuned makespan
    ft_served = [r for r in reqs if r.entry_source == "finetuned"]
    for r in ft_served:
        key_entry = svc.cache.peek(r.key)
        assert r.makespan == pytest.approx(key_entry.measured_makespan)


# ------------------------------------------------- contention-aware serving
def _small_trainer(seed=0):
    return PPOTrainer(PolicyConfig(hidden=32, gnn_layers=2, placer_layers=1,
                                   ffn=64, window=32, max_devices=8),
                      PPOConfig(num_samples=8, epochs=1), seed=seed)


def test_topology_digest_carries_contention_mode():
    """Two simulator modes never share a cache key; contention-off is the
    historical digest bit-for-bit."""
    topo = p100_topology(4)
    off = topology_fingerprint(topo)
    on = topology_fingerprint(topo, sender_contention=True)
    assert off != on
    assert off == topology_fingerprint(topo, sender_contention=False)
    # an equal topology (fresh object) digests identically per mode
    topo2 = p100_topology(4)
    assert topology_fingerprint(topo2) == off
    assert topology_fingerprint(topo2, sender_contention=True) == on


def test_contention_service_judges_with_contended_simulator():
    """A contention-mode worker's reported makespan is the *contended*
    makespan of the placement it returns (numpy-oracle cross-check), and
    its keys are disjoint from an off-mode worker's."""
    g = S.rnnlm(2, time_steps=3)
    topo = p100_topology(4).tightened(g.total_mem())
    cfg = ServeConfig(max_batch=1, num_samples=2, simulated=True,
                      finetune_iters=0, seed=0, sender_contention=True)
    svc = PlacementService(_small_trainer(), cfg, SimulatedClock())
    r = svc.submit(g, topo, arrival_t=0.0)
    svc.drain()
    assert r.source in ("zero_shot", "baseline")
    mk_ref, _, valid = simulate_ref(g, r.placement, topo,
                                    sender_contention=True)
    assert valid and np.isclose(r.makespan, mk_ref, rtol=1e-4)

    svc_off = PlacementService(_small_trainer(),
                               dataclasses.replace(cfg,
                                                   sender_contention=False),
                               SimulatedClock())
    r_off = svc_off.submit(g, topo, arrival_t=0.0)
    svc_off.drain()
    assert r_off.key[0] == r.key[0]        # same graph fingerprint
    assert r_off.key[1] != r.key[1]        # different topology digest


def test_contention_mode_flip_reinfers_with_zero_stale(tmp_path):
    """A warm store written contention-off must be fully invalidated by a
    contention-on restart (same policy!): every request re-infers, the
    stale_served audit stays 0, and flipping back still sees the
    original records."""
    trainer = _small_trainer()
    ph = policy_hash(trainer.state.params)
    graphs = [S.rnnlm(2, time_steps=3), S.rnnlm(2, time_steps=4)]
    topo = p100_topology(4)
    topo = topo.with_mem_caps(max(g.total_mem() for g in graphs) * 2)
    cfg = ServeConfig(max_batch=1, num_samples=2, simulated=True,
                      finetune_iters=0, seed=0)

    store = PersistentStore(tmp_path, ph)
    svc = PlacementService(trainer, cfg, SimulatedClock(), store=store)
    for i, g in enumerate(graphs):
        svc.submit(g, topo, arrival_t=float(i))
    svc.shutdown()
    written = store.stats.records_written
    assert written >= len(graphs)

    # mode flip: same policy, contended simulator (shutdown compaction
    # merged the publish+snapshot duplicates down to one record per key)
    store_on = PersistentStore(tmp_path, ph, worker_tag="w1",
                               sender_contention=True)
    assert store_on.stats.records_invalidated == len(graphs)
    assert len(store_on) == 0              # nothing fresh to serve
    cfg_on = dataclasses.replace(cfg, sender_contention=True)
    svc_on = PlacementService(trainer, cfg_on, SimulatedClock(),
                              store=store_on)
    assert len(svc_on.cache) == 0          # no cross-mode warm start
    srcs = []
    for i, g in enumerate(graphs):
        srcs.append(svc_on.submit(g, topo, arrival_t=float(i)).source)
    svc_on.shutdown()
    assert all(s in ("zero_shot", "baseline") for s in srcs)   # re-inferred
    assert svc_on.counts["stale_served"] == 0
    assert svc_on.counts["cache"] == 0 and svc_on.counts["disk"] == 0

    # flipping back: off-mode records are fresh again, on-mode ones are not
    store_back = PersistentStore(tmp_path, ph, worker_tag="w2")
    assert len(store_back) >= len(graphs)
    assert store_back.stats.records_invalidated >= len(graphs)  # on-mode recs


def test_service_refuses_cross_mode_store(tmp_path):
    """A service must not warm-start from a store replaying the other
    simulator mode."""
    trainer = _small_trainer()
    store = PersistentStore(tmp_path, policy_hash(trainer.state.params),
                            sender_contention=True)
    with pytest.raises(AssertionError):
        PlacementService(trainer, ServeConfig(simulated=True), store=store)


# ------------------------------------------------- jumbo bucket + rejection
def test_service_sheds_oversized_requests_typed():
    """Out-of-bounds requests degrade to the baseline fast path with a
    typed Rejection instead of crashing the worker on an assert."""
    trainer = _small_trainer()
    cfg = ServeConfig(simulated=True, max_graph_nodes=100)
    svc = PlacementService(trainer, cfg, SimulatedClock())

    # too many devices for the policy head (max_devices=8)
    g = S.rnnlm(2, time_steps=3)
    wide = p100_topology(12).tightened(g.total_mem())
    r1 = svc.submit(g, wide, arrival_t=0.0)
    assert r1.source == "shed"
    assert r1.rejection.reason == "too_many_devices"
    assert r1.rejection.limit == 8 and r1.rejection.requested == 12
    assert r1.placement.shape == (g.num_nodes,)
    assert r1.placement.max() < 12 and np.isnan(r1.makespan)

    # graph above the worker's jumbo bound
    big = S.rnnlm(2, time_steps=5)
    assert big.num_nodes > 100
    topo = p100_topology(4).tightened(big.total_mem())
    r2 = svc.submit(big, topo, arrival_t=1.0)
    assert r2.source == "shed"
    assert r2.rejection.reason == "graph_too_large"
    assert r2.placement.shape == (big.num_nodes,)

    assert svc.counts["shed_rejected"] == 2
    assert svc.counts["shed"] == 2
    # the worker is still healthy: a normal request resolves
    ok = svc.submit(g, p100_topology(4).tightened(g.total_mem()),
                    arrival_t=2.0)
    svc.drain()
    assert ok.source in ("zero_shot", "baseline")
    assert np.isfinite(ok.makespan)


def test_service_jumbo_bucket_admission():
    """Graphs above jumbo_threshold skip the micro-batcher: they are
    segment-padded (featurize.jumbo_bucket, not the power-of-two ladder)
    and served solo; the result is cached so repeats hit."""
    from repro.core.featurize import jumbo_bucket as jb
    pcfg = PolicyConfig(hidden=32, gnn_layers=2, placer_layers=1, ffn=64,
                        window=32, max_devices=8, segment=32, gnn_chunk=64)
    trainer = PPOTrainer(pcfg, PPOConfig(num_samples=4, epochs=1), seed=0)
    cfg = ServeConfig(simulated=True, num_samples=2,
                      jumbo_threshold=64, jumbo_pad_multiple=64,
                      finetune_iters=0)
    svc = PlacementService(trainer, cfg, SimulatedClock())
    g = S.rnnlm(2, time_steps=3)          # 72 nodes > 64 threshold
    assert g.num_nodes > cfg.jumbo_threshold
    topo = p100_topology(4).tightened(g.total_mem())
    r = svc.submit(g, topo, arrival_t=0.0)
    assert svc.counts["jumbo"] == 1
    assert r.source in ("zero_shot", "baseline")
    assert r.placement.shape == (g.num_nodes,)
    assert np.isfinite(r.makespan)
    # context arrays live at the segment-aligned jumbo bucket
    ctx = svc._ctx[r.key]
    assert ctx.gb.op.shape[0] == jb(g.num_nodes, 64)
    assert ctx.gb.op.shape[0] % pcfg.segment == 0
    # repeat traffic rides the cache, not another decode
    r2 = svc.submit(g, topo, arrival_t=1.0)
    assert r2.source == "cache"
    assert svc.counts["jumbo"] == 1


def test_admission_sheds_oversize_at_router():
    """Router-level jumbo shedding: AdmissionController counts and
    refuses graphs above max_graph_nodes before they reach a worker."""
    from repro.serve import AdmissionConfig, AdmissionController
    ac = AdmissionController(AdmissionConfig(max_graph_nodes=50))
    assert ac.admit(lag_s=0.0, queue_depth=0, num_nodes=10)
    assert not ac.admit(lag_s=0.0, queue_depth=0, num_nodes=51)
    assert ac.stats.shed_oversize == 1
    assert ac.stats.shed == 1
    assert ac.stats.as_dict()["shed_oversize"] == 1


# --------------------------------------------------------- retrace pinning
def _flops_scaled(g, factor):
    """Same topology/size, different content hash: a distinct cache key
    that lands in the same compiled bucket."""
    return topo_relabel(f"{g.name}-x{factor}", g.op_type, g.flops * factor,
                        g.out_bytes, g.mem_bytes, g.out_shape, g.src, g.dst)


def test_one_compile_per_bucket_on_warm_replay():
    """Retrace regression pin: a warm 20-request replay across two serving
    buckets adds ZERO new jit programs.  Each request is a distinct cache
    key (flops-scaled variant), so every one runs real batched inference —
    but the sampler compiles once per (bucket, devices, samples) config,
    never per graph.  Module-level jit caches persist across tests, so the
    pin is on deltas, not absolute cache sizes."""
    from repro.obs import jaxprof

    trainer = _small_trainer()
    cfg = ServeConfig(max_batch=1, num_samples=2, simulated=True,
                      finetune_iters=0, seed=0)
    svc = PlacementService(trainer, cfg, SimulatedClock())
    g_a = S.rnnlm(2, time_steps=3)        # 72 nodes  -> bucket 128
    g_b = S.rnnlm(2, time_steps=12)       # 261 nodes -> bucket 512
    assert bucket_size(g_a.num_nodes) != bucket_size(g_b.num_nodes)
    topo = p100_topology(4)

    t = [0.0]

    def submit(g):
        r = svc.submit(g, topo, arrival_t=t[0])
        t[0] += 1.0
        svc.drain()
        return r

    # cold: first request in each bucket compiles at most one program each
    mon_cold = jaxprof.RetraceMonitor()
    submit(g_a)
    submit(g_b)
    assert mon_cold.delta().get("serve.sample_batch", 0) <= 2

    # warm replay: 20 fresh keys across the two warmed buckets
    mon = jaxprof.RetraceMonitor()
    for i in range(10):
        ra = submit(_flops_scaled(g_a, 1.0 + 0.01 * (i + 1)))
        rb = submit(_flops_scaled(g_b, 1.0 + 0.01 * (i + 1)))
        assert ra.source == "zero_shot" and rb.source == "zero_shot"
    assert svc.counts["zero_shot"] >= 22          # replay ran real inference
    assert mon.delta() == {}                      # zero new compiles anywhere


# ------------------------------------------------------ miss-path tracing
def _inside(child, parent):
    return (parent.ts <= child.ts and
            child.ts + child.dur <= parent.ts + parent.dur)


def test_miss_path_spans_nest_under_submit():
    """On the wall clock a miss records ``serve.submit`` holding
    ``serve.fingerprint`` and ``serve.context``, which holds the simulator
    graph, featurize and baseline spans; the two children cover most of
    the submit."""
    from repro.obs.trace import Tracer, set_tracer
    from repro.serve import WallClock
    svc = PlacementService(_small_trainer(), ServeConfig(
        max_batch=2, num_samples=2, finetune_iters=0), clock=WallClock())
    g = S.rnnlm(2, time_steps=3)
    topo = p100_topology(4)
    svc.submit(g, topo)                     # compiles the bucket's programs
    svc.step(force=True)
    mine = Tracer()
    old = set_tracer(mine)
    try:
        req = svc.submit(_flops_scaled(g, 1.5), topo)
    finally:
        set_tracer(old)
    svc.step(force=True)
    assert req.source == "zero_shot"
    by = {}
    for s in mine.spans:
        by.setdefault(s.name, []).append(s)
    (sub,), (fp,), (ctx,) = (by["serve.submit"], by["serve.fingerprint"],
                             by["serve.context"])
    assert _inside(fp, sub) and _inside(ctx, sub)
    for name in ("serve.sim_graph", "serve.featurize", "serve.baselines"):
        (child,) = by[name]
        assert _inside(child, ctx), name
    assert fp.dur + ctx.dur >= 0.8 * sub.dur, (fp.dur, ctx.dur, sub.dur)


def test_zero_shot_phases_sum_to_latency():
    """Each batched zero-shot answer splits its latency into prepare,
    batch wait, policy and select, observed into ``serve_phase_seconds``;
    cache hits observe no phase."""
    from repro.serve import WallClock
    svc = PlacementService(_small_trainer(), ServeConfig(
        max_batch=2, num_samples=2, finetune_iters=0), clock=WallClock())
    g = S.rnnlm(2, time_steps=3)
    topo = p100_topology(4)
    reqs = [svc.submit(_flops_scaled(g, 1.0 + 0.1 * i), topo)
            for i in range(3)]
    svc.step(force=True)
    hit = svc.submit(g, topo)               # the first one, now cached
    assert hit.source == "cache" and hit.phases() == {}
    for r in reqs:
        assert r.source == "zero_shot"
        ph = r.phases()
        assert set(ph) == {"prepare", "batch_wait", "policy", "select"}
        assert all(v >= 0 for v in ph.values()), ph
        assert abs(sum(ph.values()) - r.latency) <= 1e-9
    h = svc.metrics.histogram("serve_phase_seconds", "", ("phase",))
    for phase in ("prepare", "batch_wait", "policy", "select"):
        assert h.count({"phase": phase}) == len(reqs)
    assert "serve_phase_seconds" in svc.snapshot()
    assert 'serve_phase_seconds_count{phase="batch_wait"} 3' in \
        svc.metrics.to_prometheus()


def test_phases_follow_the_simulated_cost_model():
    """On the simulated clock each phase is the cost model's charge: the
    lookup before the batcher, no wait for a full batch of one, one
    batched call, and a selection that costs no simulated time."""
    cfg = ServeConfig(max_batch=1, num_samples=2, simulated=True,
                      finetune_iters=0)
    svc = PlacementService(_small_trainer(), cfg, SimulatedClock())
    r = svc.submit(S.rnnlm(2, time_steps=3), p100_topology(4),
                   arrival_t=2.0)
    c = cfg.costs
    assert r.phases() == pytest.approx({
        "prepare": c.lookup_s, "batch_wait": 0.0,
        "policy": c.batch_base_s + c.batch_per_graph_s, "select": 0.0})


def test_tracing_changes_no_answer():
    """The same replay with the tracer on and off gives the same
    placements, makespans and (simulated) latencies."""
    from repro.obs.trace import Tracer, set_tracer
    g = S.rnnlm(2, time_steps=3)
    trace = [g, _flops_scaled(g, 1.3), g, _relabeled(g, 1),
             _flops_scaled(g, 1.7)]
    topo = p100_topology(4)
    trainer = _small_trainer()

    def replay(enabled):
        mine = Tracer(enabled=enabled)
        old = set_tracer(mine)
        try:
            svc = PlacementService(trainer, ServeConfig(
                max_batch=2, num_samples=2, simulated=True,
                finetune_iters=0, seed=3), SimulatedClock())
            reqs = []
            for i, gi in enumerate(trace):
                reqs.append(svc.submit(gi, topo, arrival_t=0.01 * i))
                svc.step()
            svc.drain()
        finally:
            set_tracer(old)
        return reqs, mine.spans

    on, spans = replay(True)
    off, none = replay(False)
    assert none == [] and any(s.name == "serve.submit" for s in spans)
    for a, b in zip(on, off):
        assert a.source == b.source
        np.testing.assert_array_equal(a.placement, b.placement)
        assert a.makespan == b.makespan and a.latency == b.latency
