"""Paper-scale large-graph campaign: place a >=50k-node GNMT end-to-end.

The paper's headline scalability claim is state-of-the-art placements on
hold-out graphs with over 50k nodes (8-layer GNMT) from a policy
pre-trained across graphs and superposition-fine-tuned per graph.  This
campaign reproduces that axis with the segment-native pipeline:

1. **Pre-train** a GDP-batch policy (segmented decode and chunked GNN
   aggregation, ``PolicyConfig(scale=ScaleConfig(segment=, gnn_chunk=))``)
   on a small multi-family graph set — the same compiled per-segment
   programs serve every graph size afterwards.
2. **Superposition fine-tune** a per-graph fork (``ppo.clone_state``; the
   base policy is never mutated) on each held-out large graph: 8-layer
   GNMT unrolled past 50k nodes in full mode, plus deep WaveNet /
   Transformer-XL variants.  Decode, teacher-forced PPO ratios and the
   simulator all run segment-batched, so no compiled shape ever exceeds
   the segment.
3. **Report** makespan vs ``human_expert`` / ``round_robin`` (judged by
   the same segment-batched env — bit-identical to the monolithic
   scheduler), plus wall-clock per phase and the audited peak RSS of the
   whole run.

Results print as ``large.*`` CSV lines and are written to
``BENCH_large.json`` (schema in ``docs/benchmarks.md``); the nightly CI
campaign runs quick mode and gates regressions via
``tools/check_bench_regression.py``.

The **jumbo tier** (``run_jumbo``) goes an order of magnitude past the
segment-native ceiling: real model-zoo training graphs, scan-expanded
(``extract_arch(expand=)``) to hundreds of thousands of nodes, placed
through the hierarchical coarsen→place→refine pipeline behind
``repro.api.place``.  Each jumbo row records the coarse fingerprint and
the coarse→refined makespan trajectory, so a row is reproducible from
its config hash alone.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmarks import common as C
from repro.core import baselines as B
from repro.core.policy import PolicyConfig
from repro.core.ppo import PPOConfig, PPOTrainer, clone_state
from repro.core.scale import ScaleConfig
from repro.graphs import synthetic as S
from repro.obs.metrics import RunLog
from repro.obs.trace import Tracer, get_tracer, set_tracer
from repro.sim.scheduler import SimConfig

OUT_PATH = os.environ.get("BENCH_LARGE_OUT", "BENCH_large.json")

# One compiled decode step per (segment, window) serves every graph in
# the campaign; the chunk bounds the GNN gather to O(chunk * K * H).
SEGMENT = 512
GNN_CHUNK = 2048
LARGE_SCALE = ScaleConfig(segment=SEGMENT, gnn_chunk=GNN_CHUNK)


def large_policy() -> PolicyConfig:
    """The segment-native policy config the campaign trains and serves.

    ``mask_full_devices`` is on: at 50k nodes an unconstrained decode
    fork can burn its whole fine-tune budget before drawing ONE valid
    sample (a colocation-biased policy overflows the per-device caps on
    every draw), so the campaign decodes memory-aware — every sample is
    feasible by construction and PPO spends its budget on makespan."""
    return dataclasses.replace(C.POLICY, scale=LARGE_SCALE,
                               mask_full_devices=True)


def large_ppo(num_samples: int) -> PPOConfig:
    """Fine-tune PPO config: fewer samples/epochs than the small-graph
    default — at 50k nodes each sampled placement is a full segmented
    decode, so the sample budget is the knob that sets iteration cost."""
    return dataclasses.replace(C.PPO, num_samples=num_samples, epochs=1)


# Memory slack for training AND large-graph eval: the campaign's signal
# is scale (can the policy place 50k nodes at all, and beat the blind
# baselines on speed); a tight memory cliff on 8 devices collapses the
# sampled-placement validity the policy learns from — the same rationale
# as benchmarks/transfer.py's training regime.  The paper's tight-memory
# regime is covered by table1/table2/generalization.
SLACK = 2.5


def pretrain_tasks() -> List[C.Task]:
    """Small multi-family pre-training set (segment-padded like the large
    tasks, so pre-training exercises the exact serving-time programs)."""
    specs = [
        ("rnnlm-2", S.rnnlm(2, time_steps=6), 4),
        ("gnmt-2", S.gnmt(2, time_steps=4), 4),
        ("wavenet-2", S.wavenet(2, 9), 4),
    ]
    return [C.make_task(name, g, nd, tighten=SLACK, sim=SimConfig(),
                        segment=SEGMENT)
            for name, g, nd in specs]


def large_graphs(quick: bool) -> List[Tuple[str, Any]]:
    """Held-out large graphs.  Full mode's gnmt-8 unrolls past 50k nodes
    (the paper's headline scale); quick mode keeps the same families at
    a few thousand nodes so CI finishes in minutes."""
    if quick:
        return [
            ("gnmt-8", S.gnmt(8, time_steps=24)),
            ("transformer_xl-4", S.transformer_xl(4, segments=6)),
        ]
    gnmt_big = S.gnmt(8, time_steps=352)
    assert gnmt_big.num_nodes >= 50_000, gnmt_big.num_nodes
    return [
        ("gnmt-8", gnmt_big),
        ("wavenet-deep", S.wavenet(4, 36)),
        ("transformer_xl-8", S.transformer_xl(8, segments=24)),
    ]


# ---------------------------------------------------------------------------
# Jumbo tier: scan-expanded model-zoo graphs through the hierarchical
# coarsen→place→refine pipeline (repro.hier behind repro.api.place).
# ---------------------------------------------------------------------------
SHARD_CACHE = os.environ.get("REPRO_SHARD_CACHE",
                             os.path.join(".cache", "shards"))


def jumbo_configs(quick: bool) -> List[Tuple[str, Dict[str, Any]]]:
    """Jumbo workloads: (row name, extract_arch spec + pipeline knobs).

    Quick mode's qwen3-8b backward graph (~90k nodes) keeps the nightly
    CI row under a few minutes; full mode's jamba-398B backward graph at
    seq 16384 expands past 500k nodes — the hierarchical pipeline's
    headline scale."""
    if quick:
        return [("qwen3-grad", dict(
            arch="qwen3-8b", mode="grad", seq=4096, expand=64,
            coarse_target=2048, refine_window=8192, max_windows=4))]
    return [("jamba-grad-16k", dict(
        arch="jamba-1.5-large-398b", mode="grad", seq=16384, expand=128,
        coarse_target=8192, refine_window=8192, max_windows=None))]


def _jumbo_shards(name: str, spec: Dict[str, Any]):
    """Extract (disk-cached) and shard (disk-cached) one jumbo graph."""
    from repro.graphs.jaxpr_extract import arch_digest, extract_arch
    from repro.graphs.shards import open_shards, write_shards
    digest = arch_digest(spec["arch"], mode=spec["mode"], seq=spec["seq"],
                         expand=spec["expand"])
    sdir = os.path.join(SHARD_CACHE, f"{name}-{digest[:16]}")
    sh = open_shards(sdir)
    if sh is not None:
        return sh
    g = extract_arch(spec["arch"], mode=spec["mode"], seq=spec["seq"],
                     expand=spec["expand"])
    return write_shards(g, sdir)


def run_jumbo(quick: bool = True, finetune_iters: int = 12,
              num_samples: int = 4, seed: int = 0,
              run_log: Optional[RunLog] = None) -> Dict[str, Any]:
    """One BENCH_large.json row per jumbo config.

    Each row is fully reproducible: the coarse fingerprint pins the
    coarsening, the trajectory records every refinement acceptance, and
    the extract/shard caches mean a rerun re-places without re-tracing."""
    from repro.api import Budget, place
    from repro.sim import p100_topology, prepare_sim_graph
    from repro.sim.scheduler import Env

    rows: Dict[str, Any] = {}
    for name, spec in jumbo_configs(quick):
        t0 = time.time()
        sh = _jumbo_shards(name, spec)
        n = sh.num_nodes
        cap = sh.totals["mem_bytes"] / 8 * SLACK
        topo = p100_topology(8).with_mem_caps(cap)
        sc = dataclasses.replace(LARGE_SCALE,
                                 coarse_target=spec["coarse_target"],
                                 refine_window=spec["refine_window"])
        plan = place(sh, topo, method="hierarchical",
                     pcfg=dataclasses.replace(large_policy(), scale=sc),
                     ppo=large_ppo(num_samples),
                     budget=Budget(finetune_iters=finetune_iters,
                                   samples=num_samples, seed=seed,
                                   refine_windows=spec["max_windows"]))
        place_s = time.time() - t0

        t1 = time.time()
        g = sh.load_graph()
        env = Env.from_config(prepare_sim_graph(g, topo), topo, SimConfig())
        rr_pl = B.round_robin(g, topo)
        mk, _, ok = env.rewards(np.asarray(rr_pl, np.int32)[None])
        rr = float(mk[0]) if bool(ok[0]) else float("inf")
        d_rr, beats = C.vs_baseline(plan.makespan, rr)
        row = {
            "nodes": n,
            "devices": 8,
            "arch": spec["arch"], "mode": spec["mode"],
            "seq": spec["seq"], "expand": spec["expand"],
            "coarse_nodes": spec["coarse_target"],
            "coarse_fingerprint": plan.fingerprints["coarse"],
            "graph_digest": plan.fingerprints["graph"],
            "coarse_makespan": float(plan.trajectory[0]),
            "gdp": float(plan.makespan),
            "valid": plan.valid,
            "round_robin": rr,
            "gdp_vs_round_robin": d_rr,
            "beats_rr": beats,
            "trajectory": [float(x) for x in plan.trajectory],
            "refined_windows": len(plan.trajectory) - 1,
            "place_s": place_s,
            "baseline_s": time.time() - t1,
            "wall_s": time.time() - t0,
            "peak_rss_bytes": C.peak_rss_bytes(),
        }
        if run_log is not None:
            run_log.emit(dict(row, phase="jumbo", graph=name,
                              trajectory=None))
        rows[name] = row
        print(f"jumbo.{name},{row['gdp']:.5f},nodes={n};"
              f"coarse={row['coarse_makespan']:.5f};rr={rr:.5f};"
              f"dRR={C.fmt_pct(d_rr)};"
              f"rss_gb={row['peak_rss_bytes']/2**30:.2f};"
              f"wall={row['wall_s']:.0f}s", flush=True)
    return rows


def run(quick: bool = True, pretrain_iters: int = 10,
        finetune_iters: int = 8, num_samples: int = 4,
        seed: int = 0, only: Optional[List[str]] = None,
        run_log: Optional[RunLog] = None,
        jumbo: bool = False, jumbo_only: bool = False) -> Dict[str, Any]:
    """Full campaign; returns the BENCH_large.json dict.

    ``only`` restricts the large-graph list by name (the slow tier-1
    test runs just the >=50k-node gnmt-8 to bound its wall clock);
    ``jumbo_only`` skips the classic pretrain+finetune tier entirely and
    runs just the hierarchical jumbo tier (the 1M-node full-mode row
    without the hours-long classic full campaign attached)."""
    jumbo = jumbo or jumbo_only
    # validate the filter before the expensive pre-training phase — a
    # typo (or a full-mode-only name in quick mode) would otherwise
    # surface as max() over an empty dict after minutes of work
    names = [n for n, _ in large_graphs(quick)]
    if only is not None and not set(only) & set(names):
        raise ValueError(f"only={only!r} matches no large graph in "
                         f"{'quick' if quick else 'full'} mode: {names}")
    pretrain_s = 0.0
    tasks: List[C.Task] = []
    graphs: Dict[str, Any] = {}
    if not jumbo_only:
        pcfg = large_policy()
        tr = PPOTrainer(pcfg, large_ppo(num_samples=8), seed=seed)
        tr.run_log = run_log
        tasks = pretrain_tasks()
        t0 = time.time()
        tr.train([(t.name, t.gb, t.env, t.num_devices) for t in tasks],
                 iterations=pretrain_iters, log_every=0)
        pretrain_s = time.time() - t0

    for name, g in ([] if jumbo_only else large_graphs(quick)):
        if only is not None and name not in only:
            continue
        t1 = time.time()
        task = C.make_task(name, g, 8, tighten=SLACK, segment=SEGMENT)
        base = {}
        for bname, fn in (("human", B.human_expert),
                          ("round_robin", B.round_robin)):
            pl = fn(task.graph, task.topo)
            pl_pad = np.zeros(task.gb.op.shape[0], np.int32)
            pl_pad[:g.num_nodes] = pl
            mk, ok = C.eval_placement(task, pl_pad)
            base[bname] = float(mk) if ok else float("inf")
        baseline_s = time.time() - t1

        t2 = time.time()
        zs = tr.best_of_samples(task.gb, task.env_true, task.num_devices,
                                num_samples)
        zero_shot_s = time.time() - t2

        t3 = time.time()
        fork = PPOTrainer(pcfg, large_ppo(num_samples), seed=seed + 17,
                          state=clone_state(tr.state))
        fork.run_log = run_log
        # no early-stop target when round_robin is infeasible — inf*0.95
        # is inf, which finetune() "reaches" after one iteration and
        # silently collapses the whole fine-tune budget
        rr_target = (base["round_robin"] * 0.95
                     if np.isfinite(base["round_robin"]) else None)
        res = fork.finetune(task.name, task.gb, task.env,
                            task.num_devices, finetune_iters,
                            target=rr_target)
        ft = min(res["best_makespan"],
                 fork.best_of_samples(task.gb, task.env_true,
                                      task.num_devices, num_samples))
        finetune_s = time.time() - t3

        gdp = float(min(zs, ft))
        rr = base["round_robin"]
        d_rr, beats = C.vs_baseline(gdp, rr)
        row = {
            "nodes": g.num_nodes,
            "padded_nodes": int(task.gb.op.shape[0]),
            "devices": task.num_devices,
            "zero_shot": float(zs),
            "finetune": float(ft),
            "finetune_iters_run": res["iterations"],
            "gdp": gdp,
            "round_robin": rr,
            "human": base["human"],
            "gdp_vs_round_robin": d_rr,
            "beats_rr": beats,        # None when round_robin is infeasible
            "baseline_s": baseline_s,
            "zero_shot_s": zero_shot_s,
            "finetune_s": finetune_s,
            "wall_s": time.time() - t1,
            "peak_rss_bytes": C.peak_rss_bytes(),
        }
        graphs[name] = row
        print(f"large.{name},{gdp:.5f},nodes={g.num_nodes};"
              f"zs={row['zero_shot']:.5f};ft={row['finetune']:.5f};"
              f"rr={rr:.5f};hp={base['human']:.5f};"
              f"dRR={C.fmt_pct(d_rr)};"
              f"wall={row['wall_s']:.0f}s", flush=True)

    jumbo_rows: Dict[str, Any] = {}
    if jumbo:
        jumbo_rows = run_jumbo(quick=quick, finetune_iters=finetune_iters,
                               num_samples=num_samples, seed=seed,
                               run_log=run_log)

    out = {
        "quick": quick,
        "segment": SEGMENT,
        "gnn_chunk": GNN_CHUNK,
        "pretrain_iters": pretrain_iters,
        "finetune_iters": finetune_iters,
        "num_samples": num_samples,
        "pretrain_s": pretrain_s,
        "pretrain_graphs": [t.name for t in tasks],
        "graphs": graphs,
        "jumbo": jumbo_rows,
        "max_nodes": max(r["nodes"] for r in
                         list(graphs.values()) + list(jumbo_rows.values())),
        # only genuine wins count — a graph whose round_robin baseline
        # is infeasible (beats_rr None) can't claim a beat; None when the
        # classic tier didn't run (jumbo_only)
        "all_beat_rr": (bool(all(r["beats_rr"] is True
                                 for r in graphs.values()))
                        if graphs else None),
        "peak_rss_bytes": C.peak_rss_bytes(),
    }
    beat = out["all_beat_rr"]
    print(f"large.all_beat_rr,{'na' if beat is None else int(beat)},"
          f"max_nodes={out['max_nodes']};"
          f"peak_rss_gb={out['peak_rss_bytes']/2**30:.2f}", flush=True)
    return out


def main(quick: bool = True, out: str = None,
         jumbo: bool = True, jumbo_only: bool = False) -> Dict[str, Any]:
    """CLI/campaign entry: run, write the BENCH_large.json artifact
    (strict JSON: inf becomes null).  Only a full run (>=50k-node
    GNMT-8) is cached into experiments.json — quick numbers must never
    surface as ``large.campaign.*`` lines.

    Runs with tracing enabled and writes two observability sidecars next
    to the BENCH artifact: ``*.metrics.jsonl`` (per-iteration PPO
    training records) and ``*.trace.json`` (Chrome trace-event JSON,
    loadable in Perfetto)."""
    t0 = time.time()
    out = out or OUT_PATH
    metrics_path, trace_path = C.obs_out_paths(out)
    run_log = RunLog(metrics_path, run="large")
    old_tracer = set_tracer(Tracer(enabled=True))
    try:
        results = run(quick=quick,
                      pretrain_iters=10 if quick else 60,
                      finetune_iters=8 if quick else 24,
                      num_samples=4, run_log=run_log, jumbo=jumbo,
                      jumbo_only=jumbo_only)
    finally:
        tracer = get_tracer()
        tracer.export_chrome(trace_path)
        set_tracer(old_tracer)
        run_log.close()
    results["wall_s"] = time.time() - t0
    results["obs"] = {"metrics_jsonl": metrics_path,
                      "trace_json": trace_path,
                      "spans": len(tracer.spans)}
    # a jumbo-only run is not the classic full campaign — never let it
    # masquerade as campaign-grade large.* numbers
    C.cache_section("large", results,
                    campaign_grade=not quick and not jumbo_only,
                    obs_paths=(metrics_path, trace_path))
    with open(out, "w") as f:
        json.dump(C.json_safe(results), f, indent=1, default=float,
                  allow_nan=False)
    print(f"[large] wrote {out} in {results['wall_s']:.0f}s", flush=True)
    return results


if __name__ == "__main__":
    from repro.obs.jaxprof import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help=">=50k-node GNMT-8 + deep WaveNet/Transformer-XL")
    ap.add_argument("--out", default=None,
                    help=f"artifact path (default: {OUT_PATH})")
    ap.add_argument("--no-jumbo", action="store_true",
                    help="skip the hierarchical jumbo tier")
    ap.add_argument("--jumbo-only", action="store_true",
                    help="run just the hierarchical jumbo tier")
    args = ap.parse_args()
    main(quick=not args.full, out=args.out, jumbo=not args.no_jumbo,
         jumbo_only=args.jumbo_only)
