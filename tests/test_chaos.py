"""Chaos path: fault injection, migration-aware replan, fleet-change
serving, rescale-under-churn.

The cheap tests pin the fault-injection value types (derived topologies,
schedule fingerprints, migration-bytes accounting) and the repair
heuristic without touching the policy.  The replan and cluster tests
drive real decode through a small policy — the headline guarantees
(aware replan never moves more bytes than from-scratch AND lands within
the makespan band; ``stale_served == 0`` across a fleet flip) are exact
properties of the selection rule, so they are asserted, not sampled.
"""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import baselines as B
from repro.core.policy import PolicyConfig
from repro.core.ppo import PPOConfig, PPOTrainer
from repro.core.scale import ScaleConfig
from repro.graphs import synthetic as S
from repro.serve import fingerprint as FP
from repro.serve.cluster import ClusterConfig, PlacementCluster
from repro.serve.replan import (ReplanConfig, make_replace_fn,
                                make_scratch_fn, repair_placement, replan)
from repro.serve.service import ServeConfig
from repro.sim import chaos as X
from repro.sim.device import A100, P100, multi_gen_fleet, p100_topology
from repro.sim.scheduler import Env, SimConfig, prepare_sim_graph

PCFG = PolicyConfig(hidden=32, gnn_layers=2, placer_layers=1, ffn=64,
                    window=32, max_devices=8)


def _fleet(graphs, slack=3.0):
    topo = multi_gen_fleet(((A100, 4), (P100, 4)))
    return topo.tightened(float(max(g.total_mem() for g in graphs)),
                          slack=slack)


def _params(seed=0):
    return PPOTrainer(PCFG, PPOConfig(num_samples=4), seed=seed).state.params


# ------------------------------------------------- fault injection types
def test_fail_devices_zeroes_memory_keeps_width():
    topo = p100_topology(4)
    ft = X.fail_devices(topo, (1, 3))
    assert ft.num_devices == topo.num_devices          # head width constant
    assert ft.mem_caps[1] == 0.0 and ft.mem_caps[3] == 0.0
    assert ft.mem_caps[0] == topo.mem_caps[0]
    assert list(X.alive_devices(ft)) == [0, 2]
    # a failed fleet is a DIFFERENT fleet: provenance re-keys by itself
    assert FP.topology_fingerprint(ft) != FP.topology_fingerprint(topo)


def test_degrade_links_scales_bandwidth_and_rekeys():
    topo = p100_topology(4)
    dt = X.degrade_links(topo, {(0, 1): 0.1})
    assert np.isclose(dt.bw[0, 1], topo.bw[0, 1] * 0.1)
    assert np.isclose(dt.bw[1, 0], topo.bw[1, 0])      # directed
    assert FP.topology_fingerprint(dt) != FP.topology_fingerprint(topo)


def test_failure_schedule_fingerprint_and_state():
    ev = (X.FleetEvent(10.0, "fail", (1, 5)),
          X.FleetEvent(20.0, "degrade", links=((0, 2),), bw_scale=0.25),
          X.FleetEvent(30.0, "restore", (1,)))
    s1, s2 = X.FailureSchedule(ev, seed=0), X.FailureSchedule(ev, seed=0)
    assert s1.fingerprint() == s2.fingerprint()        # value identity
    assert s1.fingerprint() != X.FailureSchedule(ev, seed=1).fingerprint()
    assert s1.fingerprint() != X.FailureSchedule(ev[:2], seed=0).fingerprint()
    assert s1.failed_at(5.0) == frozenset()
    assert s1.failed_at(15.0) == frozenset({1, 5})
    assert s1.failed_at(35.0) == frozenset({5})        # 1 restored
    assert s1.link_scales_at(25.0) == {(0, 2): 0.25}
    assert s1.times() == [10.0, 20.0, 30.0]
    topo = p100_topology(8)
    t_mid = s1.topology_at(topo, 25.0)
    assert t_mid.mem_caps[1] == 0.0 and t_mid.mem_caps[5] == 0.0
    assert np.isclose(t_mid.bw[0, 2], topo.bw[0, 2] * 0.25)
    # before the first event the derived fleet IS the base fleet
    assert FP.topology_fingerprint(s1.topology_at(topo, 0.0)) == \
        FP.topology_fingerprint(topo)


def test_migration_bytes_accounting():
    g = S.rnnlm(1, time_steps=3)
    old = np.zeros(g.num_nodes, np.int32)
    new = old.copy()
    new[0] = 1                                         # one by-choice move
    moved, forced = X.migration_bytes(g, old, new)
    assert moved == pytest.approx(float(g.mem_bytes[0]))
    assert forced == 0.0
    # kill the old home: every node's restore is forced, none by choice
    moved_f, forced_f = X.migration_bytes(g, old, new, failed=(0,))
    assert moved_f == 0.0
    assert forced_f == pytest.approx(float(g.mem_bytes.sum()))


def test_repair_placement_moves_only_dead_nodes():
    g = S.inception(modules=2)
    topo = _fleet([g])
    rng = np.random.RandomState(0)
    inc = rng.randint(0, 8, g.num_nodes).astype(np.int32)
    rep = repair_placement(g, X.fail_devices(topo, (2, 6)), inc, (2, 6))
    on_dead = np.isin(inc, (2, 6))
    assert np.array_equal(rep[~on_dead], inc[~on_dead])  # survivors stay
    assert not np.isin(rep, (2, 6)).any()                # dead avoided
    assert on_dead.any()                                 # test exercised


# ------------------------------------------------------ replan guarantees
def test_replan_headline_properties_exact():
    """The band-constrained lexicographic selection rule guarantees the
    chaos-benchmark headline by construction: never more moved bytes
    than the from-scratch baseline, makespan within the slack band."""
    params = _params()
    g = S.rnnlm(2, time_steps=4)
    topo = _fleet([g])
    rcfg = ReplanConfig(num_samples=4, seed=3)
    inc = replan(params, PCFG, g, topo, B.round_robin(g, topo), (),
                 rcfg=dataclasses.replace(rcfg, scratch_only=True)).placement
    ftopo = X.fail_devices(topo, (1, 5))
    aware = replan(params, PCFG, g, ftopo, inc, (1, 5), rcfg=rcfg)
    scratch = replan(params, PCFG, g, ftopo, inc, (1, 5),
                     rcfg=dataclasses.replace(rcfg, scratch_only=True))
    assert aware.valid and scratch.valid
    assert not np.isin(aware.placement, (1, 5)).any()   # decode masks dead
    assert aware.moved_bytes <= scratch.moved_bytes + 1e-9
    assert aware.makespan <= (1 + rcfg.makespan_slack) * scratch.makespan \
        + 1e-12
    # the result self-reports the baseline it was banded against
    assert aware.scratch_makespan == pytest.approx(scratch.makespan)
    # deterministic: same (graph, fleet, incumbent, failure, seed) replays
    again = replan(params, PCFG, g, ftopo, inc, (1, 5), rcfg=rcfg)
    assert np.array_equal(again.placement, aware.placement)
    assert again.makespan == aware.makespan
    assert again.source == aware.source


def test_replan_repair_wins_when_in_band():
    """With a sticky incumbent (already valid on the survivors) the
    repair candidate moves zero by-choice bytes — whenever it lands in
    the makespan band nothing can beat it lexicographically."""
    params = _params()
    g = S.rnnlm(2, time_steps=4)
    topo = _fleet([g], slack=6.0)                       # roomy survivors
    rcfg = ReplanConfig(num_samples=4, seed=0)
    inc = replan(params, PCFG, g, topo, B.round_robin(g, topo), (),
                 rcfg=dataclasses.replace(rcfg, scratch_only=True)).placement
    res = replan(params, PCFG, g, X.fail_devices(topo, (1,)), inc, (1,),
                 rcfg=dataclasses.replace(rcfg, makespan_slack=10.0))
    assert res.valid
    assert res.source == "repair"
    assert res.moved_bytes == 0.0


# ------------------------------------------------- recovery trajectories
def _schedule():
    return X.FailureSchedule((
        X.FleetEvent(10.0, "fail", (1, 5)),
        X.FleetEvent(20.0, "degrade", links=((0, 2), (2, 0)), bw_scale=0.25),
        X.FleetEvent(30.0, "restore", (1,)),
    ), seed=0)


def test_recovery_trajectory_deterministic_and_valid():
    params = _params()
    g = S.rnnlm(2, time_steps=4)
    topo = _fleet([g])
    rcfg = ReplanConfig(num_samples=4, seed=0)
    init = replan(params, PCFG, g, topo, B.round_robin(g, topo), (),
                  rcfg=dataclasses.replace(rcfg, scratch_only=True)).placement
    fn = make_replace_fn(params, PCFG, rcfg=rcfg)
    t1 = X.recovery_trajectory(g, topo, _schedule(), init, fn)
    t2 = X.recovery_trajectory(g, topo, _schedule(), init, fn)
    assert len(t1) == 3
    for a, b in zip(t1, t2):                            # bit-identical
        assert np.array_equal(a.placement, b.placement)
        assert a.makespan == b.makespan
        assert a.moved_bytes == b.moved_bytes
    for s in t1:
        assert s.valid
        assert not np.isin(s.placement, list(s.failed)).any()
    # the scratch baseline replays deterministically too
    sf = make_scratch_fn(params, PCFG, rcfg=rcfg)
    s1 = X.recovery_trajectory(g, topo, _schedule(), init, sf)
    s2 = X.recovery_trajectory(g, topo, _schedule(), init, sf)
    assert all(np.array_equal(a.placement, b.placement)
               for a, b in zip(s1, s2))


def test_recovery_trajectory_segmented_matches_monolithic():
    """Segmented decode + segmented simulation must reproduce the
    monolithic recovery trajectory bit-for-bit — chaos does not get to
    weaken the paper's segmentation invariant."""
    params = _params()
    g = S.transformer_xl(2, segments=2)
    topo = _fleet([g])
    rcfg = ReplanConfig(num_samples=4, seed=1)
    init = replan(params, PCFG, g, topo, B.round_robin(g, topo), (),
                  rcfg=dataclasses.replace(rcfg, scratch_only=True)).placement
    seg_cfg = dataclasses.replace(PCFG, scale=ScaleConfig(segment=16))
    mono = X.recovery_trajectory(
        g, topo, _schedule(), init, make_replace_fn(params, PCFG, rcfg=rcfg))
    seg = X.recovery_trajectory(
        g, topo, _schedule(), init,
        make_replace_fn(params, seg_cfg, rcfg=rcfg), segment=16)
    assert len(mono) == len(seg) == 3
    for a, b in zip(mono, seg):
        assert np.array_equal(a.placement, b.placement)
        assert a.makespan == b.makespan
        assert a.valid == b.valid
        assert a.moved_bytes == b.moved_bytes


# -------------------------------------------- failure modes are provenance
def test_every_comm_mode_bumps_topology_fingerprint():
    topo = p100_topology(4)
    combos = [dict(sender_contention=s, receiver_contention=r,
                   jittered_bandwidth=j)
              for s in (False, True) for r in (False, True)
              for j in (False, True)]
    fps = [FP.topology_fingerprint(topo, **kw) for kw in combos]
    assert len(set(fps)) == len(combos)                # all 8 distinct
    # jitter_amp/seed are part of the jittered fleet's identity ...
    assert FP.topology_fingerprint(topo, jittered_bandwidth=True,
                                   jitter_seed=1) != \
        FP.topology_fingerprint(topo, jittered_bandwidth=True, jitter_seed=0)
    # ... and ignored when jitter is off (historical digests untouched)
    assert FP.topology_fingerprint(topo, jitter_seed=1) == \
        FP.topology_fingerprint(topo)


def test_mode_bits_packing():
    assert SimConfig().mode_bits == 0
    assert SimConfig(sender_contention=True).mode_bits == 1
    assert SimConfig(receiver_contention=True).mode_bits == 2
    assert SimConfig(jittered_bandwidth=True).mode_bits == 4
    assert SimConfig(sender_contention=True, receiver_contention=True,
                     jittered_bandwidth=True).mode_bits == 7


@pytest.mark.parametrize("mode", ["receiver_contention",
                                  "jittered_bandwidth"])
def test_mode_flip_invalidates_persisted_records(tmp_path, mode):
    """Records persisted under one communication mode must never be
    served under another: reopening a store with flipped ``mode_bits``
    invalidates them (same machinery as a policy bump)."""
    tr = PPOTrainer(PCFG, PPOConfig(num_samples=2), seed=0)
    graphs = [S.rnnlm(2, time_steps=3)]
    topo = _fleet(graphs)
    on = ServeConfig(max_batch=1, max_wait_s=0.0, num_samples=2,
                     finetune_iters=0, simulated=True, **{mode: True})
    cl = PlacementCluster(tr, ClusterConfig(num_workers=1, serve=on),
                          store_root=tmp_path)
    cl.submit(graphs[0], topo, arrival_t=0.0)
    cl.drain()
    assert cl.stats()["stale_served"] == 0
    cl.shutdown()
    off = dataclasses.replace(on, **{mode: False})
    cl2 = PlacementCluster(tr, ClusterConfig(num_workers=1, serve=off),
                           store_root=tmp_path)
    assert cl2.workers[0].store.stats.records_invalidated >= 1
    r = cl2.submit(graphs[0], topo, arrival_t=0.0)
    cl2.drain()
    assert r.source in ("zero_shot", "baseline")        # re-measured
    assert cl2.stats()["stale_served"] == 0


# ------------------------------------- cluster fleet change under traffic
def test_cluster_fleet_change_and_rescale_under_churn(tmp_path):
    """The serving tier reacts to a fleet failure: old-fleet cache lines
    invalidated, hot graphs re-placed migration-aware and published
    under the new fleet fingerprint, post-failure traffic all cache hits
    with no dead devices; grow/shrink rescales mid-traffic never lose a
    record and ``stale_served`` stays 0 throughout."""
    tr = PPOTrainer(PCFG, PPOConfig(num_samples=4), seed=0)
    graphs = [S.rnnlm(2, time_steps=3), S.inception(modules=2),
              S.transformer_xl(2, segments=2)]
    topo = _fleet(graphs)
    cfg = ClusterConfig(num_workers=2, serve=ServeConfig(
        max_batch=1, max_wait_s=0.0, num_samples=2, finetune_iters=0,
        simulated=True))
    cl = PlacementCluster(tr, cfg, store_root=tmp_path)
    t = 0.0
    for g in graphs:
        cl.submit(g, topo, arrival_t=t)
        t += 0.1
    cl.drain()

    failed = (1, 5)
    ftopo = X.fail_devices(topo, failed)
    summary = cl.on_fleet_change(topo, ftopo, failed=failed)
    assert summary["old_fp"] != summary["new_fp"]
    assert summary["replaced"] == len(graphs)
    assert summary["invalidated"] >= 1

    post = []
    for g in graphs:
        post.append(cl.submit(g, ftopo, arrival_t=t))
        t += 0.1
    cl.drain()
    assert all(r.source == "cache" for r in post)       # warm under new fp
    assert all(r.key[1] == summary["new_fp"] for r in post)
    assert all(not np.isin(r.placement, failed).any() for r in post)

    # grow mid-traffic, then shrink below the starting width
    grew = cl.rescale(3)
    assert grew["new_workers"] == 3
    for g in graphs:
        cl.submit(g, ftopo, arrival_t=t)
        t += 0.1
    cl.drain()
    shrunk = cl.rescale(1)
    assert shrunk["new_workers"] == 1 and len(cl.workers) == 1
    last = [cl.submit(g, ftopo, arrival_t=t + i * 0.1)
            for i, g in enumerate(graphs)]
    cl.drain()
    # nothing previously computed is recomputed or lost across rescales
    assert all(r.source in ("cache", "disk") for r in last)
    st = cl.stats()
    assert st["stale_served"] == 0
    assert st["fleet_events"] == 1
    assert st["rescales"] == 2
    assert st["served_total"] == len(cl.completed())
    cl.shutdown()


# ---------------------------------------------- scheduler under dead fleet
@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_simulator_agrees_on_failed_fleets(seed):
    """The jitted scheduler and the numpy oracle agree on a derived
    (partially failed + degraded) fleet — fault injection reuses the
    pinned simulator rather than forking its semantics."""
    from repro.sim import simulate
    from repro.sim.reference import simulate_ref
    from repro.sim.scheduler import SimTopology

    import jax.numpy as jnp
    g = S.rnnlm(2, time_steps=4)
    base = _fleet([g])
    topo = X.degrade_links(X.fail_devices(base, (2,)), {(0, 1): 0.5})
    alive = list(X.alive_devices(topo))
    rng = np.random.RandomState(seed)
    p = np.asarray(alive, np.int32)[rng.randint(0, len(alive), g.num_nodes)]
    sg = prepare_sim_graph(g, topo, max_deg=16)
    mk, util, valid = simulate(sg, jnp.asarray(p),
                               SimTopology.from_topology(topo))
    mk_ref, util_ref, valid_ref = simulate_ref(g, p, topo)
    assert np.isclose(float(mk), mk_ref, rtol=1e-4)
    # utilization is mem/cap and a dead device's cap is 0: both sides
    # yield NaN there by the same arithmetic — only the agreement matters
    assert np.isclose(float(util), util_ref, rtol=1e-5, equal_nan=True)
    assert bool(valid) == valid_ref
