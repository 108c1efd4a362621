"""Topology-transfer campaign: train on one fleet, place on another.

GDP's headline claim is *transfer*: one policy, trained once, generalizes
to placement problems it never saw.  The paper measures transfer across
held-out **graphs**; this campaign measures it across held-out **device
fleets** — the axis a serving tier actually rides (new hardware
generations arrive, the graphs stay).

Protocol, per simulator mode (``sender_contention`` off and on, a
:class:`~repro.sim.scheduler.SimConfig` field — contended makespans are
not comparable to uncontended ones, so each mode is its own campaign):

1. **Train** a GDP-batch policy on a small graph set placed on the
   *training fleet* — an NVLink-island / PCIe / InfiniBand hierarchy
   (``nvlink_host_ib_topology``, 8 uniform GPUs, non-uniform links).
2. **Zero-shot** the frozen policy onto each *held-out fleet*
   (``cpu_gpu_topology``: 3 GPUs + a slow big-memory CPU host;
   ``multi_gen_fleet``: 2 fast A100 + 2 slow P100) — fleets with device
   *speed* asymmetry the training fleet never exhibited.  Both a graph
   seen in training and an unseen graph are placed (graph+fleet double
   transfer).
3. **Superposition fine-tune** a per-graph fork of the policy
   (``ppo.clone_state``; the base policy is never mutated — the same
   escalation the serving ladder runs) for a few dozen iterations.

Every method — GDP, ``human_expert``, ``metis_like``, the topology-blind
``round_robin`` control — is judged by the same simulator under the same
``SimConfig``, so with contention on the baselines pay for their link
hot-spots too.  The headline check (also asserted by the slow tier-1
test): the trained policy beats ``round_robin`` on at least one held-out
fleet in *both* modes.  A fleet where ``round_robin`` itself OOMs does
not count — ``beats_rr`` is None there, so the headline flag reflects
only genuine makespan wins.

Results are printed as ``transfer.*`` CSV lines and written to
``BENCH_transfer.json`` (schema in ``docs/benchmarks.md``).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Dict, List, Tuple

from benchmarks import common as C
from repro.core.ppo import PPOTrainer, clone_state
from repro.graphs import synthetic as S
from repro.obs.metrics import RunLog
from repro.obs.trace import Tracer, get_tracer, set_tracer
from repro.sim.device import (A100, P100, Topology, cpu_gpu_topology,
                              multi_gen_fleet, nvlink_host_ib_topology)
from repro.sim.scheduler import SimConfig

OUT_PATH = os.environ.get("BENCH_TRANSFER_OUT", "BENCH_transfer.json")


def train_fleet() -> Topology:
    """The training fleet: 8 uniform P100s, NVLink islands of 2 bridged
    by PCIe inside each host, InfiniBand between the two hosts.  Links
    are non-uniform but every device runs at the same speed — speed
    asymmetry is exactly what the held-out fleets add."""
    return nvlink_host_ib_topology(num_hosts=2, gpus_per_host=4, spec=P100,
                                   island=2, nvlink_bw=100e9)


def holdout_fleets() -> Dict[str, Topology]:
    """The zero-shot target fleets (never seen in training)."""
    return {
        "cpu_gpu": cpu_gpu_topology(num_gpus=3, num_cpus=1),
        "multi_gen": multi_gen_fleet(((A100, 2), (P100, 2))),
    }


def _train_graphs(full: bool) -> List[Any]:
    ts = 8 if full else 5
    return [
        S.rnnlm(2, time_steps=ts),
        S.inception(modules=6 if full else 4),
        S.wavenet(2, 12 if full else 8),
    ]


def _eval_graphs(full: bool) -> Dict[str, Any]:
    """One graph the policy trained on (topology transfer only) and one
    it never saw (graph + topology double transfer)."""
    return {
        "seen": S.rnnlm(2, time_steps=8 if full else 5),
        "unseen": S.transformer_xl(2, segments=3 if full else 2),
    }


def _mode_label(sender_contention: bool) -> str:
    return "contention_on" if sender_contention else "contention_off"


def run_mode(sender_contention: bool, pretrain_iters: int,
             finetune_iters: int, full: bool = False,
             seed: int = 0, run_log: RunLog = None) -> Dict[str, Any]:
    """One full transfer campaign under a single simulator mode."""
    sim = SimConfig(sender_contention=sender_contention)
    tfleet = train_fleet()
    # Training runs with relaxed memory (slack 2.5): the transfer signal
    # is the link structure, and a tight cliff on 8 devices collapses the
    # sampled-placement validity the policy learns from.  The held-out
    # eval tasks keep the paper's tight regime.
    train_tasks = [
        C.make_task_topo(f"train-{g.name}", g,
                         tfleet.tightened(g.total_mem(), slack=2.5), sim=sim)
        for g in _train_graphs(full)]

    tr = PPOTrainer(C.POLICY, C.PPO, seed=seed)
    tr.run_log = run_log
    t0 = time.time()
    tr.train([(t.name, t.gb, t.env, t.num_devices) for t in train_tasks],
             iterations=pretrain_iters, log_every=0)
    train_s = time.time() - t0

    fleets: Dict[str, Any] = {}
    for fname, ftopo in holdout_fleets().items():
        rows: Dict[str, Any] = {}
        for role, g in _eval_graphs(full).items():
            task = C.make_task_topo(f"{fname}-{role}", g,
                                    ftopo.tightened(g.total_mem()), sim=sim)
            base = C.baseline_rows(task)
            zs = tr.best_of_samples(task.gb, task.env_true,
                                    task.num_devices, 16)
            fork = PPOTrainer(C.POLICY, C.PPO, seed=seed + 7,
                              state=clone_state(tr.state))
            fork.run_log = run_log
            t1 = time.time()
            res = fork.finetune(task.name, task.gb, task.env,
                                task.num_devices, finetune_iters)
            ft = min(res["best_makespan"],
                     fork.best_of_samples(task.gb, task.env_true,
                                          task.num_devices, 16))
            gdp = float(min(zs, ft))
            rr = base["round_robin"]
            # beats_rr is None (not True) when round_robin itself OOMs:
            # an infeasible baseline is not a makespan win.
            d_rr, beats = C.vs_baseline(gdp, rr)
            rows[role] = {
                "nodes": task.graph.num_nodes,
                "devices": task.num_devices,
                "zero_shot": float(zs), "finetune": float(ft), "gdp": gdp,
                "finetune_s": time.time() - t1,
                "round_robin": rr, "human": base["human"],
                "metis": base["metis"],
                "gdp_vs_round_robin": d_rr,
                "beats_rr": beats,
            }
            print(f"transfer.{_mode_label(sender_contention)}."
                  f"{fname}.{role},{gdp:.5f},"
                  f"zs={rows[role]['zero_shot']:.5f};"
                  f"ft={rows[role]['finetune']:.5f};"
                  f"rr={rr:.5f};hp={base['human']:.5f};"
                  f"dRR={C.fmt_pct(d_rr)}",
                  flush=True)
        rows["beats_rr"] = bool(any(r["beats_rr"] is True
                                    for r in rows.values()
                                    if isinstance(r, dict)))
        fleets[fname] = rows

    out = {
        "sender_contention": sender_contention,
        "train_fleet": "nvlink_host_ib(2 hosts x 4 P100, island=2)",
        "train_graphs": [t.name for t in train_tasks],
        "pretrain_iters": pretrain_iters,
        "finetune_iters": finetune_iters,
        "train_s": train_s,
        "fleets": fleets,
        "any_holdout_beats_rr": bool(any(f["beats_rr"]
                                         for f in fleets.values())),
    }
    print(f"transfer.{_mode_label(sender_contention)}.any_holdout_beats_rr,"
          f"{int(out['any_holdout_beats_rr'])},target=1", flush=True)
    return out


def run(pretrain_iters: int = 30, finetune_iters: int = 15,
        full: bool = False, seed: int = 0,
        modes: Tuple[bool, ...] = (False, True),
        run_log: RunLog = None) -> Dict[str, Any]:
    """Both simulator modes; returns the BENCH_transfer.json dict."""
    return {_mode_label(m): run_mode(m, pretrain_iters, finetune_iters,
                                     full=full, seed=seed, run_log=run_log)
            for m in modes}


def main(quick: bool = True, out: str = None) -> Dict[str, Any]:
    """CLI/campaign entry: run, write the BENCH_transfer.json artifact
    (strict JSON: OOM/inf becomes null).  Only a full-budget run is
    cached into experiments.json — quick numbers must never surface as
    ``transfer.campaign.*`` lines.

    Runs with tracing enabled and writes two observability sidecars next
    to the BENCH artifact: ``*.metrics.jsonl`` (per-iteration PPO
    training records) and ``*.trace.json`` (Chrome trace-event JSON,
    loadable in Perfetto)."""
    t0 = time.time()
    out = out or OUT_PATH
    metrics_path, trace_path = C.obs_out_paths(out)
    run_log = RunLog(metrics_path, run="transfer")
    old_tracer = set_tracer(Tracer(enabled=True))
    try:
        results = run(pretrain_iters=30 if quick else 200,
                      finetune_iters=15 if quick else 50, full=not quick,
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
    C.cache_section("transfer", results, campaign_grade=not quick,
                    obs_paths=(metrics_path, trace_path))
    with open(out, "w") as f:
        json.dump(C.json_safe(results), f, indent=1, default=float,
                  allow_nan=False)
    print(f"[transfer] wrote {out} in {results['wall_s']:.0f}s",
          flush=True)
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
