"""Serving benchmark: throughput, latency/hit-rate sweeps, makespan regret.

Three sections:

* **throughput** (wall clock, fresh jit caches): serve a mixed workload of
  distinct-size graphs through (a) the micro-batching service and (b) a
  naive one-graph-at-a-time inference loop (featurize at the exact graph
  size, jit, sample, select best by simulator — what a client without the
  serving layer would write).  The service buckets every shape-dependent
  program, so its compile count is O(buckets) while the naive loop compiles
  per distinct graph size; the headline ratio (target: >=5x) is dominated
  by exactly the compile+dispatch amortization a continuous-batching LM
  server sells.  Steady-state per-call numbers are reported alongside so
  the two effects are not conflated.
* **sweep** (simulated clock, deterministic): request-rate x zipf-skew grid
  of p50/p99 latency and cache hit rate.
* **regret** (simulated clock): repeat a zipf trace over a fixed graph pool
  with fine-tune escalation on; per-pass mean makespan regret vs a
  per-graph fine-tuned oracle must shrink monotonically as the cache warms
  toward fine-tuned placements.

Results are printed as ``name,value,derived`` CSV lines and written to
``BENCH_serve.json`` (CI uploads ``BENCH_*.json`` as artifacts).

``--cluster`` runs the **multi-host tier** instead (``serve.cluster``)
and writes ``BENCH_serve_cluster.json``: 1->4 worker throughput scaling
(target >=3x at 4 workers), warm-restart hit-rate recovery from the
persistent store, policy-bump provenance invalidation (zero stale
placements served), and overload p99 with vs without admission control.
All cluster numbers run under simulated clocks, so they are exact
functions of the trace.  ``docs/serving.md`` explains how to read both
artifacts.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import tempfile
import time
from functools import partial
from typing import Any, Dict, List

import jax
import numpy as np

from benchmarks import common as C
from repro.core import policy as policy_mod
from repro.core.featurize import bucket_size, featurize
from repro.core.policy import PolicyConfig
from repro.core.ppo import PPOConfig, PPOTrainer, clone_state
from repro.graphs import synthetic as S
from repro.obs.metrics import RunLog, counters_flat
from repro.obs.trace import Tracer, get_tracer, set_tracer
from repro.serve import (AdmissionConfig, ClusterConfig, PlacementCluster,
                         PlacementService, ServeConfig, SimulatedClock)
from repro.sim.device import p100_topology
from repro.sim.scheduler import Env, prepare_sim_graph

POLICY = PolicyConfig(hidden=32, gnn_layers=2, placer_layers=1, ffn=64,
                      window=32, max_devices=8)
PPO = PPOConfig(num_samples=8, epochs=1)

OUT_PATH = os.environ.get("BENCH_SERVE_OUT", "BENCH_serve.json")
CLUSTER_OUT_PATH = os.environ.get("BENCH_SERVE_CLUSTER_OUT",
                                  "BENCH_serve_cluster.json")


def _mixed_workload(count: int) -> List[Any]:
    """Mixed-family graphs, every entry a distinct (N, K) compiled shape,
    all inside ONE padding bucket (128): the naive path pays one XLA
    compile per entry while the bucketed service compiles once total.
    Uniquely named so oracle/regret bookkeeping can key on ``name``."""
    cands = [
        S.rnnlm(2, time_steps=3), S.rnnlm(2, time_steps=4),
        S.rnnlm(2, time_steps=5), S.rnnlm(3, time_steps=3),
        S.rnnlm(4, time_steps=2), S.gnmt(2, time_steps=2),
        S.inception(modules=3), S.inception(modules=4),
        S.inception(modules=5), S.wavenet(1, 9), S.wavenet(2, 5),
        S.wavenet(1, 8),
    ]
    for g in cands:            # rename BEFORE replicating: slots beyond 12
        g.name = f"{g.name}-n{g.num_nodes}"   # share objects (repeat keys)
    return (cands * (count // len(cands) + 1))[:count]


def _trainer(seed: int = 0) -> PPOTrainer:
    return PPOTrainer(POLICY, PPO, seed=seed)


# ------------------------------------------------------------- throughput
@partial(jax.jit, static_argnames=("pcfg", "nd", "ns"))
def _naive_sample(params, pcfg, gb, nd, key, ns, temp):
    return policy_mod.sample(params, pcfg, gb, nd, key, ns, temp)


def run_throughput(num_requests: int = 12, num_samples: int = 2,
                   max_batch: int = 4) -> Dict[str, float]:
    """Burst of concurrent requests (the regime batching exists for): the
    whole burst is submitted, then the service drains.  The naive loop
    answers the same burst one graph at a time.  Both paths run the same
    featurize -> sample -> simulator-select pipeline with cold jit caches;
    the service's cache is no help here (every key is distinct) — the win
    is bucketed batching amortizing compiles and dispatch."""
    graphs = _mixed_workload(num_requests)
    topo = p100_topology(4)
    topo = topo.with_mem_caps(max(g.total_mem() for g in graphs) * 2)

    # --- one-graph-at-a-time: exact-size featurize + jit per shape
    tr = _trainer()
    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    naive_shapes = set()
    for g in graphs:
        gb = featurize(g, max_deg=8, topo=topo)
        naive_shapes.add((gb.op.shape[0], gb.nbr_idx.shape[1]))
        pls, _ = _naive_sample(tr.state.params, POLICY, gb, 4, key,
                               num_samples, 0.25)
        sg = prepare_sim_graph(g, topo, max_deg=16)
        mks, _, valid = Env(sg, topo).rewards(pls)
        jax.block_until_ready(mks)
    naive_s = time.perf_counter() - t0

    # --- micro-batched service (zero-shot only: no fine-tune escalation)
    svc = PlacementService(_trainer(), ServeConfig(
        max_batch=max_batch, max_wait_s=1e9, num_samples=num_samples,
        finetune_iters=0))
    t0 = time.perf_counter()
    for g in graphs:
        svc.submit(g, topo)        # burst arrival; full groups flush inline
    svc.drain()
    served_s = time.perf_counter() - t0
    assert len(svc.completed) == num_requests

    # --- steady state: same shapes again, all programs warm
    t0 = time.perf_counter()
    for g in graphs:
        gb = featurize(g, max_deg=8, topo=topo)
        pls, _ = _naive_sample(tr.state.params, POLICY, gb, 4, key,
                               num_samples, 0.25)
        jax.block_until_ready(pls)
    naive_steady_s = time.perf_counter() - t0

    row = {
        "requests": num_requests,
        "distinct_shapes": len(naive_shapes),
        "naive_s": naive_s,
        "served_s": served_s,
        "throughput_naive_rps": num_requests / naive_s,
        "throughput_served_rps": num_requests / served_s,
        "speedup": naive_s / served_s,
        "naive_steady_s_per_graph": naive_steady_s / num_requests,
        "served_stats": svc.stats(),
    }
    print(f"serve.throughput,{row['speedup']:.2f},"
          f"naive={row['throughput_naive_rps']:.2f}rps;"
          f"batched={row['throughput_served_rps']:.2f}rps;"
          f"shapes={row['distinct_shapes']};target>=5x", flush=True)
    return row


# ------------------------------------------------------------------ sweep
def _zipf_trace(pool: List[Any], num_requests: int, skew: float,
                rate_rps: float, seed: int = 0):
    """(arrival_t, graph) stream with zipf-skewed popularity."""
    rng = np.random.RandomState(seed)
    ranks = np.arange(1, len(pool) + 1, dtype=np.float64)
    probs = ranks ** -skew
    probs /= probs.sum()
    picks = rng.choice(len(pool), size=num_requests, p=probs)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, size=num_requests))
    return [(float(arrivals[i]), pool[picks[i]]) for i in range(num_requests)]


def run_sweep(pool_size: int = 6, num_requests: int = 40,
              rates=(1.0, 10.0, 100.0), skews=(0.5, 1.2)) -> List[Dict]:
    """Deterministic rate x zipf-skew grid of latency and hit rate."""
    pool = _mixed_workload(pool_size)
    topo = p100_topology(4)
    topo = topo.with_mem_caps(max(g.total_mem() for g in pool) * 2)
    rows = []
    for skew in skews:
        for rate in rates:
            svc = PlacementService(_trainer(), ServeConfig(
                max_batch=4, max_wait_s=0.02, num_samples=2,
                finetune_iters=0, simulated=True), SimulatedClock())
            for t, g in _zipf_trace(pool, num_requests, skew, rate):
                svc.submit(g, topo, arrival_t=t)
                svc.step()
            svc.drain()
            st = svc.stats()
            row = {"rate_rps": rate, "zipf_skew": skew,
                   "hit_rate": st["hit_rate"],
                   "p50_s": st.get("latency_p50_s", float("nan")),
                   "p99_s": st.get("latency_p99_s", float("nan"))}
            rows.append(row)
            print(f"serve.sweep.rate{rate:g}.skew{skew:g},"
                  f"{row['p50_s']:.4f},p99={row['p99_s']:.4f};"
                  f"hit={row['hit_rate']:.2f}", flush=True)
    return rows


# ----------------------------------------------------------------- regret
def run_regret(pool_size: int = 3, passes: int = 3, reqs_per_pass: int = 8,
               finetune_iters: int = 6, oracle_iters: int = 12,
               seed: int = 0) -> Dict[str, Any]:
    """Repeat a zipf trace; regret vs per-graph fine-tuned oracle must
    shrink as escalations publish fine-tuned placements into the cache."""
    pool = _mixed_workload(pool_size)
    topo = p100_topology(4)
    topo = topo.with_mem_caps(max(g.total_mem() for g in pool) * 2)

    # oracle: per-graph fine-tune with a larger budget than the service
    oracle: Dict[str, float] = {}
    base = _trainer(seed)
    for g in pool:
        pad_n = bucket_size(g.num_nodes)
        sg = prepare_sim_graph(g, topo, max_deg=16, pad_to=pad_n)
        gb = featurize(g, max_deg=8, pad_to=pad_n, topo=topo)
        fork = PPOTrainer(POLICY, PPO, seed=seed + 1,
                          state=clone_state(base.state))
        res = fork.finetune(g.name, gb, Env(sg, topo, shaped_reward=True),
                            4, oracle_iters)
        oracle[g.name] = res["best_makespan"]

    svc = PlacementService(_trainer(seed), ServeConfig(
        max_batch=4, max_wait_s=0.02, num_samples=2, simulated=True,
        finetune_iters=finetune_iters, escalate_margin=0.0, seed=seed),
        SimulatedClock())
    rng = np.random.RandomState(seed)
    picks = rng.choice(pool_size, size=reqs_per_pass,
                       p=(np.arange(1, pool_size + 1) ** -1.2) /
                       (np.arange(1, pool_size + 1) ** -1.2).sum())
    per_pass = []
    t_base = 0.0
    for p in range(passes):
        start = len(svc.completed)
        for j, pick in enumerate(picks):
            svc.submit(pool[pick], topo, arrival_t=t_base + j * 0.1)
            svc.step()
        svc.drain()
        t_base = svc.clock.now() + 10.0
        regs = [(r.makespan - oracle[r.graph.name]) / oracle[r.graph.name]
                for r in svc.completed[start:]]
        per_pass.append(float(np.mean(regs)))
        print(f"serve.regret.pass{p},{per_pass[-1]:.4f},"
              f"hit={svc.stats()['hit_rate']:.2f}", flush=True)
    monotone = all(per_pass[i + 1] <= per_pass[i] + 1e-9
                   for i in range(len(per_pass) - 1))
    print(f"serve.regret.monotone,{int(monotone)},passes={passes}",
          flush=True)
    return {"oracle": oracle, "per_pass_regret": per_pass,
            "monotone_shrink": monotone, "stats": svc.stats()}


# ---------------------------------------------------------------- cluster
# legacy cluster stats() keys checked bit-for-bit against the merged
# registry snapshot (the tentpole's acceptance invariant)
_PARITY_LADDER = ("cache", "disk", "zero_shot", "baseline", "finetunes",
                  "finetune_published", "forward_adopted", "stale_served")
_PARITY_ADMISSION = ("admitted", "shed_lag", "shed_depth", "shed_oversize")


def parity_snapshot(cl: PlacementCluster) -> Dict[str, Any]:
    """Merged metrics snapshot of ``cl``, asserted bit-for-bit equal to
    the legacy ``stats()`` counters it replaced.

    A mismatch here means the registry-backed counts have drifted from
    the stats() schema the BENCH baselines pin — fail loudly.
    """
    st = cl.stats()
    snap = cl.snapshot()
    flat = counters_flat(snap)
    mismatches = {}
    for k in _PARITY_LADDER:
        v = flat.get(f'serve_events_total{{event="{k}"}}', 0)
        if v != st[k]:
            mismatches[f"ladder.{k}"] = (v, st[k])
    for k in ("forwarded", "shed"):
        v = flat.get(f'cluster_router_total{{event="{k}"}}', 0)
        if v != st[k]:
            mismatches[f"router.{k}"] = (v, st[k])
    for k in _PARITY_ADMISSION:
        v = flat.get(f'admission_decisions_total{{decision="{k}"}}', 0)
        if v != st[k]:
            mismatches[f"admission.{k}"] = (v, st[k])
    assert not mismatches, f"metrics/stats parity broken: {mismatches}"
    return snap


def _emit_cluster_obs(obs_log, section: str, cl: PlacementCluster) -> None:
    """Parity-check one cluster and stream its snapshot to the sidecar."""
    if obs_log is None:
        parity_snapshot(cl)
        return
    obs_log.emit({"section": section, "parity": "ok",
                  "snapshot": parity_snapshot(cl)})


def _cluster_pool(num_keys: int) -> List[Any]:
    """``num_keys`` distinct-fingerprint rnnlm variants in ONE padding
    bucket: cost perturbations change the WL fingerprint (each variant is
    its own cache key) but not the compiled shape, so the whole pool
    shares one XLA program per (batch, D) and the cluster numbers measure
    serving, not compilation."""
    out = []
    for i in range(num_keys):
        g = S.rnnlm(2, time_steps=3)
        g.flops = g.flops * (1.0 + 0.002 * (i + 1))
        g.name = f"rnnlm-k{i}"
        out.append(g)
    return out


def _mk_cluster(trainer: PPOTrainer, num_workers: int, store_root=None,
                max_lag_s: float = math.inf,
                max_batch: int = 1) -> PlacementCluster:
    return PlacementCluster(trainer, ClusterConfig(
        num_workers=num_workers, virtual_nodes=128,
        serve=ServeConfig(max_batch=max_batch, max_wait_s=0.0,
                          num_samples=2, finetune_iters=0, simulated=True),
        admission=AdmissionConfig(max_lag_s=max_lag_s)),
        store_root=store_root)


def run_cluster_scaling(trainer: PPOTrainer, pool: List[Any], topo,
                        repeats: int = 3, obs_log=None) -> Dict[str, Any]:
    """One burst trace replayed through 1/2/4-worker clusters; aggregate
    throughput must scale near-linearly (>=3x at 4 workers)."""
    trace = pool * repeats
    rows: Dict[str, Any] = {}
    for n in (1, 2, 4):
        cl = _mk_cluster(trainer, n)
        for g in trace:
            cl.submit(g, topo, arrival_t=0.0)
        cl.drain()
        st = cl.stats()
        assert st["served_total"] == len(trace)
        _emit_cluster_obs(obs_log, f"scaling.{n}w", cl)
        rows[f"{n}w"] = {
            "workers": n, "makespan_s": st["makespan_s"],
            "throughput_rps": len(trace) / st["makespan_s"],
            "keys_per_worker": [p["unique_keys"] for p in st["per_worker"]],
            "zero_shot": st["zero_shot"], "hit_rate": st["hit_rate"],
            "stale_served": st["stale_served"],
        }
        print(f"serve.cluster.scaling.{n}w,"
              f"{rows[f'{n}w']['throughput_rps']:.1f},"
              f"makespan={st['makespan_s']:.3f}s;"
              f"keys={rows[f'{n}w']['keys_per_worker']}", flush=True)
    rows["speedup_4w"] = (rows["4w"]["throughput_rps"] /
                          rows["1w"]["throughput_rps"])
    rows["speedup_2w"] = (rows["2w"]["throughput_rps"] /
                          rows["1w"]["throughput_rps"])
    print(f"serve.cluster.scaling.speedup,{rows['speedup_4w']:.2f},"
          f"2w={rows['speedup_2w']:.2f};target>=3x", flush=True)
    return rows


def run_cluster_restart(trainer: PPOTrainer, pool: List[Any], topo,
                        store_root, sweeps: int = 3,
                        obs_log=None) -> Dict[str, Any]:
    """Warm-restart recovery: steady-state hit rate before shutdown vs
    the FIRST sweep after restarting from the persistent store, then a
    policy bump that must invalidate (not serve) every stored entry."""
    def sweep(cl, t0):
        srcs = []
        for j, g in enumerate(pool):
            srcs.append(cl.submit(g, topo, arrival_t=t0 + j * 0.01).source)
        cl.drain()
        return sum(s in ("cache", "disk") for s in srcs) / len(srcs)

    cl = _mk_cluster(trainer, 2, store_root=store_root)
    rates = [sweep(cl, p * 10.0) for p in range(sweeps)]
    steady = rates[-1]
    cl.shutdown()

    # every worker replays ALL segments under the shared root, so each
    # store's invalidation counter already covers the whole cluster:
    # take max, not sum (sum would multiply by num_workers)
    warm = _mk_cluster(trainer, 2, store_root=store_root)
    recovery = sweep(warm, 0.0)
    stw = warm.stats()
    inval_warm = max(svc.store.stats.records_invalidated
                     for svc in warm.workers)
    warm.shutdown()

    bumped_tr = _trainer(seed=1234)
    bumped = _mk_cluster(bumped_tr, 2, store_root=store_root)
    bump_rate = sweep(bumped, 0.0)
    stb = bumped.stats()
    inval_bump = max(svc.store.stats.records_invalidated
                     for svc in bumped.workers)
    _emit_cluster_obs(obs_log, "warm_restart.bumped", bumped)
    row = {
        "per_sweep_hit_rate": rates, "steady_hit_rate": steady,
        "restart_first_sweep_hit_rate": recovery,
        "recovered": recovery >= steady - 1e-9,
        "restart_zero_shot": stw["zero_shot"],
        "restart_invalidated": inval_warm,
        "restart_stale_served": stw["stale_served"],
        "bump_invalidated": inval_bump,
        "bump_zero_shot": stb["zero_shot"],
        "bump_first_sweep_hit_rate": bump_rate,
        "bump_stale_served": stb["stale_served"],
    }
    print(f"serve.cluster.restart,{recovery:.2f},"
          f"steady={steady:.2f};recovered={row['recovered']};"
          f"restart_infer={stw['zero_shot']}", flush=True)
    print(f"serve.cluster.policy_bump,{inval_bump},"
          f"reinfer={stb['zero_shot']};"
          f"stale_served={stb['stale_served']};target_stale=0", flush=True)
    return row


def run_cluster_overload(trainer: PPOTrainer, pool: List[Any], topo,
                         num_requests: int = 200, rate_rps: float = 1000.0,
                         max_lag_s: float = 0.2,
                         obs_log=None) -> Dict[str, Any]:
    """Single worker far past capacity, with vs without admission
    control: shedding to the degraded baseline fast path must bound p99
    near ``max_lag_s`` + one flush while the unbounded run's tail grows
    with the backlog."""
    trace = _zipf_trace(pool, num_requests, skew=1.1, rate_rps=rate_rps,
                        seed=3)
    rows: Dict[str, Any] = {}
    for label, lag in (("admission", max_lag_s), ("unbounded", math.inf)):
        cl = _mk_cluster(trainer, 1, max_lag_s=lag)
        for t, g in trace:
            cl.submit(g, topo, arrival_t=t)
        cl.drain()
        st = cl.stats()
        _emit_cluster_obs(obs_log, f"overload.{label}", cl)
        served = [r for r in cl.completed() if r.source != "shed"]
        # stats() now reports the shed-excluded tail itself (the cluster
        # percentile bugfix); keep the independent recompute as a check
        lat = np.asarray([r.latency for r in served], np.float64)
        p99_served = float(np.percentile(lat, 99)) if lat.size else None
        if lat.size:
            assert abs(st["served_latency_p99_s"] - p99_served) < 1e-12, (
                st["served_latency_p99_s"], p99_served)
        rows[label] = {
            "p50_s": st["latency_p50_s"], "p99_s": st["latency_p99_s"],
            "p99_served_s": p99_served,
            "served_latency_p99_s": st.get("served_latency_p99_s"),
            "shed_fraction": st["shed"] / num_requests,
            "served": len(served),
        }
        print(f"serve.cluster.overload.{label},{st['latency_p99_s']:.4f},"
              f"p99_served={rows[label]['p99_served_s']:.4f};"
              f"shed={rows[label]['shed_fraction']:.2f}", flush=True)
    costs = ServeConfig().costs
    bound = (max_lag_s + costs.batch_base_s + costs.batch_per_graph_s +
             costs.lookup_s + costs.store_lookup_s)
    rows["p99_bound_s"] = bound
    rows["bounded"] = rows["admission"]["p99_s"] <= bound + 1e-9
    rows["tail_ratio"] = (rows["unbounded"]["p99_s"] /
                          max(rows["admission"]["p99_s"], 1e-12))
    print(f"serve.cluster.overload.bounded,{int(rows['bounded'])},"
          f"bound={bound:.3f}s;tail_ratio={rows['tail_ratio']:.1f}x",
          flush=True)
    return rows


def run_cluster(quick: bool = True,
                out_path: str = None) -> Dict[str, Any]:
    """All cluster sections; returns the BENCH_serve_cluster.json dict.

    Runs with tracing enabled and writes two observability sidecars next
    to the BENCH artifact: ``*.metrics.jsonl`` (per-section merged
    registry snapshots, each parity-checked bit-for-bit against the
    legacy ``stats()`` counters) and ``*.trace.json`` (Chrome trace-event
    JSON of the whole run, loadable in Perfetto).
    """
    num_keys = 48 if quick else 64
    pool = _cluster_pool(num_keys)
    topo = p100_topology(4)
    topo = topo.with_mem_caps(max(g.total_mem() for g in pool) * 2)
    trainer = _trainer()
    metrics_path, trace_path = C.obs_out_paths(out_path or CLUSTER_OUT_PATH)
    obs_log = RunLog(metrics_path, run="serve_cluster")
    old_tracer = set_tracer(Tracer(enabled=True))
    results: Dict[str, Any] = {}
    try:
        results["scaling"] = run_cluster_scaling(
            trainer, pool, topo, repeats=3 if quick else 5,
            obs_log=obs_log)
        store_root = tempfile.mkdtemp(prefix="bench_serve_cluster_store_")
        try:
            results["warm_restart"] = run_cluster_restart(
                trainer, pool[:12], topo, store_root, obs_log=obs_log)
        finally:
            shutil.rmtree(store_root, ignore_errors=True)
        results["overload"] = run_cluster_overload(
            trainer, pool[:24], topo,
            num_requests=200 if quick else 1000, obs_log=obs_log)
    finally:
        tracer = get_tracer()
        tracer.export_chrome(trace_path)
        set_tracer(old_tracer)
        obs_log.close()
    results["obs"] = {"metrics_jsonl": metrics_path,
                      "trace_json": trace_path,
                      "spans": len(tracer.spans)}
    print(f"serve.cluster.obs,{len(tracer.spans)},"
          f"metrics={metrics_path};trace={trace_path}", flush=True)
    return results


# ------------------------------------------------------------------- main
def run(quick: bool = True) -> Dict[str, Any]:
    """All single-worker sections; returns the BENCH_serve.json dict."""
    results: Dict[str, Any] = {}
    results["throughput"] = run_throughput(
        num_requests=12, num_samples=2 if quick else 4)
    results["sweep"] = run_sweep(
        pool_size=4 if quick else 8,
        num_requests=24 if quick else 200)
    results["regret"] = run_regret(
        pool_size=2 if quick else 4,
        passes=3 if quick else 5,
        reqs_per_pass=6 if quick else 16,
        finetune_iters=4 if quick else 10,
        oracle_iters=8 if quick else 30)
    return results


def main():
    """CLI: default runs the single-worker sections; ``--cluster`` runs
    the multi-host tier and writes BENCH_serve_cluster.json instead."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--cluster", action="store_true",
                    help="run the multi-host cluster sections")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    t0 = time.time()
    if args.cluster:
        out = args.out or CLUSTER_OUT_PATH
        results = run_cluster(quick=not args.full, out_path=out)
    else:
        out = args.out or OUT_PATH
        results = run(quick=not args.full)
    results["wall_s"] = time.time() - t0
    with open(out, "w") as f:
        json.dump(C.json_safe(results), f, indent=1, default=float,
                  allow_nan=False)
    print(f"[serve] wrote {out} in {results['wall_s']:.0f}s", flush=True)


if __name__ == "__main__":
    from repro.obs.jaxprof import enable_compile_cache
    enable_compile_cache()
    main()
