"""Chaos campaign: kill devices mid-fleet, measure the recovery story.

A placement policy that only ever sees healthy fleets is half a system:
production fleets lose devices, and what matters then is (a) how fast a
good placement on the degraded fleet is found, (b) how good it is, and
(c) how many bytes of resident state the recovery ships around.  This
campaign pins all three against the obvious baseline — re-planning from
scratch as if no state existed.

Protocol (fleet: 8 heterogeneous devices, 4×A100 + 4×P100):

1. **Train** a GDP-batch policy briefly on the healthy fleet.
2. **Place** each eval graph on the healthy fleet (best valid of a
   sampled pool) — that placement is the *incumbent*: where every
   node's state lives when disaster strikes.
3. **Kill K=2 of 8 devices** and re-place two ways:

   * *migration-aware* (``serve.replan``): repair + incumbent-biased +
     scratch candidates, band-constrained lexicographic winner;
   * *from-scratch*: best-makespan valid sample, incumbent ignored.

   Per graph we report recovery makespan, replan wall-clock latency and
   by-choice migration bytes for both.  By construction the aware replan
   never moves more bytes than from-scratch AND lands within
   ``makespan_slack`` (5%) of its recovery makespan — the two headline
   flags the nightly gate pins at 1.
4. **Replay a full failure schedule** (fail 2 → degrade a link →
   restore 1) through ``sim.chaos.recovery_trajectory`` with the aware
   replanner — every step must be valid and avoid dead devices.
5. **Serving tier under chaos**: a 2-worker cluster takes traffic, the
   fleet change fires (``PlacementCluster.on_fleet_change``: stale
   entries invalidated, hot graphs re-placed migration-aware), traffic
   resumes on the degraded fleet (must be all cache hits), then the
   tier rescales 2→3→1 mid-traffic.  ``stale_served`` must stay 0
   throughout — failure modes are provenance.

Results are printed as ``chaos.*`` CSV lines and written to
``BENCH_chaos.json`` (schema in ``docs/benchmarks.md``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks import common as C
from repro.core import baselines as B
from repro.core.ppo import PPOTrainer
from repro.graphs import synthetic as S
from repro.obs.metrics import RunLog
from repro.obs.trace import Tracer, get_tracer, set_tracer
from repro.serve.cluster import ClusterConfig, PlacementCluster
from repro.serve.replan import ReplanConfig, make_replace_fn, replan
from repro.serve.service import ServeConfig
from repro.sim import chaos as X
from repro.sim.device import A100, P100, Topology, multi_gen_fleet
from repro.sim.scheduler import SimConfig

OUT_PATH = os.environ.get("BENCH_CHAOS_OUT", "BENCH_chaos.json")

KILL = (1, 5)        # K=2 of 8: one A100, one P100


def chaos_fleet(mem_total: float) -> Topology:
    """8-device heterogeneous fleet, memory-tightened but with slack for
    losing 2 of 8 devices (the survivors must be able to hold the graph,
    or there is no recovery to measure)."""
    topo = multi_gen_fleet(((A100, 4), (P100, 4)))
    return topo.tightened(mem_total, slack=3.0)


def _eval_graphs(full: bool) -> List[Any]:
    return [
        S.rnnlm(2, time_steps=8 if full else 5),
        S.inception(modules=5 if full else 3),
        S.transformer_xl(2, segments=3 if full else 2),
    ]


def _initial_placement(params, g, topo: Topology, sim: SimConfig,
                       rcfg: ReplanConfig) -> np.ndarray:
    """Best valid sampled placement on the healthy fleet (the incumbent
    every recovery starts from)."""
    res = replan(params, C.POLICY, g, topo, B.round_robin(g, topo), (),
                 sim=sim,
                 rcfg=dataclasses.replace(rcfg, scratch_only=True))
    assert res.valid, f"no valid healthy placement for {g.name}"
    return res.placement


def run(pretrain_iters: int = 12, full: bool = False, seed: int = 0,
        run_log: RunLog = None) -> Dict[str, Any]:
    """The whole chaos campaign; returns the BENCH_chaos.json dict."""
    sim = SimConfig()
    graphs = _eval_graphs(full)
    fleet = chaos_fleet(float(max(g.total_mem() for g in graphs)))
    # bias must clear the logit scale after x mem_frac (mean ~0.04 on
    # this fleet) for stickiness to bite; 256 ~= +10 logits on the mean
    # node, so biased draws deviate from the incumbent only where the
    # policy really wants to.
    rcfg = ReplanConfig(num_samples=16 if full else 8, migration_bias=256.0,
                        seed=seed)

    # 1) a briefly-trained policy (placements must be better than noise
    # for the recovery numbers to mean anything)
    tasks = [C.make_task_topo(f"chaos-{g.name}", g, fleet, sim=sim)
             for g in graphs]
    tr = PPOTrainer(C.POLICY, C.PPO, seed=seed)
    tr.run_log = run_log
    t0 = time.time()
    tr.train([(t.name, t.gb, t.env, t.num_devices) for t in tasks],
             iterations=pretrain_iters, log_every=0)
    train_s = time.time() - t0
    params = tr.state.params

    # 2-3) kill K=2, replan both ways
    ftopo = X.fail_devices(fleet, KILL)
    rows: Dict[str, Any] = {}
    for g in graphs:
        incumbent = _initial_placement(params, g, fleet, sim, rcfg)
        aware = replan(params, C.POLICY, g, ftopo, incumbent, KILL,
                       sim=sim, rcfg=rcfg)
        scratch = replan(params, C.POLICY, g, ftopo, incumbent, KILL,
                         sim=sim,
                         rcfg=dataclasses.replace(rcfg, scratch_only=True))
        assert aware.valid and scratch.valid, g.name
        mk_ratio = aware.makespan / scratch.makespan
        mv_ratio = (aware.moved_bytes / scratch.moved_bytes
                    if scratch.moved_bytes > 0
                    else float(aware.moved_bytes == 0))
        rows[g.name] = {
            "nodes": g.num_nodes,
            "aware_makespan": aware.makespan,
            "aware_moved_bytes": aware.moved_bytes,
            "aware_latency_s": aware.latency_s,
            "aware_source": aware.source,
            "scratch_makespan": scratch.makespan,
            "scratch_moved_bytes": scratch.moved_bytes,
            "scratch_latency_s": scratch.latency_s,
            "forced_bytes": aware.forced_bytes,
            "makespan_ratio": mk_ratio,
            "moved_bytes_ratio": mv_ratio,
        }
        print(f"chaos.recovery.{g.name},{aware.makespan:.5f},"
              f"scratch={scratch.makespan:.5f};"
              f"moved={aware.moved_bytes:.3g}/{scratch.moved_bytes:.3g};"
              f"lat={aware.latency_s:.2f}s;src={aware.source}", flush=True)

    # 4) full failure schedule through the aware replanner
    sched = X.FailureSchedule((
        X.FleetEvent(10.0, "fail", KILL),
        X.FleetEvent(20.0, "degrade", links=((0, 2), (2, 0)), bw_scale=0.25),
        X.FleetEvent(30.0, "restore", (KILL[0],)),
    ), seed=seed)
    g0 = graphs[0]
    traj = X.recovery_trajectory(
        g0, fleet, sched, _initial_placement(params, g0, fleet, sim, rcfg),
        make_replace_fn(params, C.POLICY, sim=sim, rcfg=rcfg), sim=sim)
    traj_rows = [{"t": s.t, "failed": list(s.failed),
                  "makespan": s.makespan, "valid": s.valid,
                  "moved_bytes": s.moved_bytes,
                  "forced_bytes": s.forced_bytes} for s in traj]
    traj_ok = all(s.valid for s in traj) and all(
        not np.isin(s.placement, list(s.failed)).any() for s in traj)
    print(f"chaos.trajectory.{g0.name},{int(traj_ok)},"
          f"events={len(traj)};fp={sched.fingerprint()[:12]}", flush=True)

    # 5) serving tier under the same failure, then rescale mid-traffic
    serve_row = _serve_under_chaos(tr, graphs, fleet, ftopo)

    mean_lat = float(np.mean([r["aware_latency_s"] for r in rows.values()]))
    total_aware = sum(r["aware_moved_bytes"] for r in rows.values())
    total_scratch = sum(r["scratch_moved_bytes"] for r in rows.values())
    headline = {
        "aware_beats_scratch_bytes": int(all(
            r["aware_moved_bytes"] <= r["scratch_moved_bytes"]
            for r in rows.values())),
        "recovery_within_5pct": int(all(
            r["makespan_ratio"] <= 1.05 + 1e-9 for r in rows.values())),
        "migration_bytes_ratio": (total_aware / total_scratch
                                  if total_scratch > 0 else 0.0),
        "replan_latency_mean_s": mean_lat,
        "trajectory_all_valid": int(traj_ok),
    }
    print(f"chaos.headline.aware_beats_scratch_bytes,"
          f"{headline['aware_beats_scratch_bytes']},target=1", flush=True)
    print(f"chaos.headline.recovery_within_5pct,"
          f"{headline['recovery_within_5pct']},target=1", flush=True)
    print(f"chaos.headline.migration_bytes_ratio,"
          f"{headline['migration_bytes_ratio']:.3f},lower=better", flush=True)
    print(f"chaos.serve.stale_served,{serve_row['stale_served']},target=0",
          flush=True)
    return {
        "fleet": "multi_gen(4xA100+4xP100)", "killed": list(KILL),
        "pretrain_iters": pretrain_iters, "train_s": train_s,
        "schedule_fingerprint": sched.fingerprint(),
        "recovery": rows, "trajectory": traj_rows,
        "serve": serve_row, "headline": headline,
    }


def _serve_under_chaos(tr: PPOTrainer, graphs: List[Any], fleet: Topology,
                       ftopo: Topology) -> Dict[str, Any]:
    """Cluster tier: fleet change + rescales under continued traffic."""
    with tempfile.TemporaryDirectory() as root:
        cfg = ClusterConfig(num_workers=2, serve=ServeConfig(
            simulated=True, num_samples=4, finetune_iters=0))
        cl = PlacementCluster(tr, cfg, store_root=root)
        t = 0.0
        for g in graphs:
            cl.submit(g, fleet, arrival_t=t)
            t += 0.1
        cl.drain()
        t1 = time.perf_counter()
        change = cl.on_fleet_change(fleet, ftopo, failed=KILL)
        change_s = time.perf_counter() - t1
        post: List[str] = []
        for g in graphs:
            post.append(cl.submit(g, ftopo, arrival_t=t).source)
            t += 0.1
        cl.drain()
        cl.rescale(3)
        for g in graphs:
            cl.submit(g, ftopo, arrival_t=t)
            t += 0.1
        cl.drain()
        cl.rescale(1)
        st = cl.stats()
        cl.shutdown()
    row = {
        "stale_served": int(st["stale_served"]),
        "fleet_invalidated": int(st["fleet_invalidated"]),
        "fleet_replaced": int(st["fleet_replaced"]),
        "rehomed": int(st["rehomed"]),
        "fleet_change_s": change_s,
        "post_failure_sources": post,
        "post_failure_all_cached": int(all(s == "cache" for s in post)),
        "served_total": int(st["served_total"]),
        "replan_sources": change["sources"],
    }
    print(f"chaos.serve.post_failure_all_cached,"
          f"{row['post_failure_all_cached']},"
          f"replaced={row['fleet_replaced']};"
          f"invalidated={row['fleet_invalidated']};"
          f"rehomed={row['rehomed']}", flush=True)
    return row


def main(quick: bool = True, out: str = None) -> Dict[str, Any]:
    """CLI/campaign entry: run, write the BENCH_chaos.json artifact
    (strict JSON) plus the observability sidecars (``*.metrics.jsonl``
    training records, ``*.trace.json`` Chrome trace).  Only full-budget
    runs are cached into experiments.json as campaign-grade."""
    t0 = time.time()
    out = out or OUT_PATH
    metrics_path, trace_path = C.obs_out_paths(out)
    run_log = RunLog(metrics_path, run="chaos")
    old_tracer = set_tracer(Tracer(enabled=True))
    try:
        results = run(pretrain_iters=12 if quick else 80, full=not quick,
                      run_log=run_log)
    finally:
        tracer = get_tracer()
        tracer.export_chrome(trace_path)
        set_tracer(old_tracer)
        run_log.close()
    results["wall_s"] = time.time() - t0
    results["obs"] = {"metrics_jsonl": metrics_path,
                      "trace_json": trace_path,
                      "spans": len(tracer.spans)}
    C.cache_section("chaos", results, campaign_grade=not quick,
                    obs_paths=(metrics_path, trace_path))
    with open(out, "w") as f:
        json.dump(C.json_safe(results), f, indent=1, default=float,
                  allow_nan=False)
    print(f"[chaos] wrote {out} in {results['wall_s']:.0f}s", flush=True)
    return results


if __name__ == "__main__":
    from repro.obs.jaxprof import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default=None,
                    help=f"artifact path (default: {OUT_PATH})")
    args = ap.parse_args()
    main(quick=not args.full, out=args.out)
