"""Benchmark orchestrator — one section per paper table/figure + roofline.

Default is quick mode (minutes on one CPU core); ``--full`` reproduces the
long campaign.  Longer cached campaign results (results/experiments.json,
produced by ``benchmarks/campaign.py``) are merged into the report when
present.  Output format: ``name,value,derived`` CSV lines per section.
"""
from __future__ import annotations

import argparse
import time


def _section(title):
    print(f"\n==== {title} " + "=" * max(0, 60 - len(title)), flush=True)


def main() -> None:
    """Run every benchmark section (quick by default; ``--full`` for the
    long campaign; ``--skip-rl`` reports cached numbers + roofline only)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip-rl", action="store_true",
                    help="only report cached RL results + roofline")
    args = ap.parse_args()
    quick = not args.full
    t0 = time.time()

    from benchmarks import common as C
    # only provenance-verified campaign sections may print as
    # `*.campaign.*` — a quick/sub-budget run that landed in the cache
    # (or a stale cache file) must not masquerade as campaign numbers
    raw = C.load_cached()
    provenance = raw.pop(C.PROVENANCE_KEY, {})
    cached = {}
    for name, section in raw.items():
        if C.is_campaign_grade(name, section, provenance.get(name)):
            cached[name] = section
        else:
            print(f"[run] cached section {name!r} lacks campaign-grade "
                  f"provenance — ignored", flush=True)

    _section("Table 1: GDP-one vs HP/METIS/HDP (live quick run)")
    if not args.skip_rl:
        from benchmarks import table1_individual
        rows = table1_individual.run(iterations=40 if quick else 400,
                                     tasks=C.paper_tasks(full=not quick)[:4 if quick else 8])
        for name, r in rows.items():
            print(f"table1.{name},{r['gdp_one']:.5f},"
                  f"hp={r['human']:.5f};hdp={r['hdp']:.5f};"
                  f"dHP={r['speedup_vs_hp']*100:+.1f}%;"
                  f"dHDP={r['speedup_vs_hdp']*100:+.1f}%")
    if "table1" in cached:
        print("-- cached campaign (longer search):")
        for name, r in cached["table1"].items():
            print(f"table1.campaign.{name},{r['gdp_one']:.5f},"
                  f"hp={r['human']:.5f};hdp={r['hdp']:.5f};"
                  f"dHP={r['speedup_vs_hp']*100:+.1f}%;"
                  f"dHDP={r['speedup_vs_hdp']*100:+.1f}%;"
                  f"search_x={r.get('search_speedup_vs_hdp', float('nan')):.1f}")

    _section("Table 2: GDP-batch vs GDP-one")
    if not args.skip_rl:
        from benchmarks import table2_batch
        rows = table2_batch.run(iterations=30 if quick else 300)
        for name, r in rows.items():
            print(f"table2.{name},{r['gdp_batch']:.5f},"
                  f"one={r['gdp_one']:.5f};d={r['batch_speedup']*100:+.1f}%")
    if "table2" in cached:
        for name, r in cached["table2"].items():
            print(f"table2.campaign.{name},{r['gdp_batch']:.5f},"
                  f"one={r['gdp_one']:.5f};d={r['batch_speedup']*100:+.1f}%")

    _section("Fig 2: generalization (zero-shot + finetune on hold-out)")
    if not args.skip_rl:
        from benchmarks import generalization
        rows = generalization.run(pretrain_iters=25 if quick else 200,
                                  finetune_iters=15 if quick else 50)
        for name, r in rows.items():
            print(f"gen.{name},{r['finetune']:.5f},"
                  f"zs={r['zero_shot']:.5f};hp={r['human']:.5f}")
    if "generalization" in cached:
        for name, r in cached["generalization"].items():
            print(f"gen.campaign.{name},{r['finetune']:.5f},"
                  f"zs={r['zero_shot']:.5f};hp={r['human']:.5f}")

    _section("Fig 3: ablations (attention / superposition)")
    if not args.skip_rl:
        from benchmarks import ablation
        rows = ablation.run(iterations=25 if quick else 300)
        for name, r in rows.items():
            print(f"ablation.{name},{r.get('full', float('nan')):.5f},"
                  f"no_attn={r.get('no_attention', float('nan')):.5f};"
                  f"no_sp={r.get('no_superposition', float('nan')):.5f}")
    if "ablation" in cached:
        for name, r in cached["ablation"].items():
            print(f"ablation.campaign.{name},{r.get('full', float('nan')):.5f},"
                  f"no_attn={r.get('no_attention', float('nan')):.5f};"
                  f"no_sp={r.get('no_superposition', float('nan')):.5f}")

    _section("Heterogeneous fleets: GDP vs topology-blind round-robin")
    if not args.skip_rl:
        from benchmarks import hetero
        rows = hetero.run(iterations=25 if quick else 300, full=not quick)
        for name, r in rows.items():
            print(f"hetero.{name},{r['gdp']:.5f},"
                  f"rr={r['round_robin']:.5f};hp={r['human']:.5f};"
                  f"metis={r['metis']:.5f};"
                  f"dRR={C.fmt_pct(r['gdp_vs_round_robin'])}")
        u = hetero.uniform_equivalence_row()
        print(f"hetero.uniform_check,{u['makespan']:.5f},valid={u['valid']}")
    if "hetero" in cached:
        for name, r in cached["hetero"].items():
            print(f"hetero.campaign.{name},{r['gdp']:.5f},"
                  f"rr={r['round_robin']:.5f};"
                  f"dRR={C.fmt_pct(r['gdp_vs_round_robin'])}")

    _section("Topology transfer: train one fleet, zero-shot another")
    if not args.skip_rl:
        from benchmarks import transfer
        tr_rows = transfer.run(pretrain_iters=20 if quick else 200,
                               finetune_iters=10 if quick else 50,
                               full=not quick)
        for mode, r in tr_rows.items():
            for fname, fr in r["fleets"].items():
                for role in ("seen", "unseen"):
                    row = fr[role]
                    print(f"transfer.{mode}.{fname}.{role},{row['gdp']:.5f},"
                          f"zs={row['zero_shot']:.5f};"
                          f"rr={row['round_robin']:.5f};"
                          f"dRR={C.fmt_pct(row['gdp_vs_round_robin'])}")
            print(f"transfer.{mode}.any_holdout_beats_rr,"
                  f"{int(r['any_holdout_beats_rr'])},target=1")
    if "transfer" in cached:
        for mode in ("contention_off", "contention_on"):
            r = cached["transfer"].get(mode)
            if r:
                print(f"transfer.campaign.{mode},"
                      f"{int(r['any_holdout_beats_rr'])},"
                      f"fleets={','.join(r['fleets'])}")

    _section("Paper-scale graphs: segmented pipeline on large GNMT")
    if not args.skip_rl:
        from benchmarks import large_graph
        lg = large_graph.run(quick=quick,
                             pretrain_iters=10 if quick else 60,
                             finetune_iters=8 if quick else 24)
        # rows print themselves as large.* CSV lines
    if "large" in cached:
        lgc = cached["large"]
        for name, r in lgc.get("graphs", {}).items():
            print(f"large.campaign.{name},{r['gdp']:.5f},"
                  f"nodes={r['nodes']};rr={r['round_robin']:.5f};"
                  f"dRR={C.fmt_pct(r['gdp_vs_round_robin'])}")
        print(f"large.campaign.peak_rss_gb,"
              f"{lgc.get('peak_rss_bytes', 0)/2**30:.2f},"
              f"max_nodes={lgc.get('max_nodes', 0)}")

    _section("Serving: batched throughput / latency sweep / regret")
    if not args.skip_rl:
        from benchmarks import serve
        serve.run(quick=quick)     # prints serve.* CSV lines itself
    if "serve" in cached:
        s = cached["serve"]
        th = s.get("throughput", {})
        print(f"serve.campaign.throughput,{th.get('speedup', float('nan')):.2f},"
              f"shapes={th.get('distinct_shapes', 0)}")
        reg = s.get("regret", {})
        print(f"serve.campaign.regret,"
              f"{';'.join(f'{x:.3f}' for x in reg.get('per_pass_regret', []))},"
              f"monotone={reg.get('monotone_shrink')}")

    _section("Serving cluster: 1->4 worker scaling / restart / overload")
    if not args.skip_rl:
        from benchmarks import serve as serve_mod
        serve_mod.run_cluster(quick=quick)   # prints serve.cluster.* lines
    if "serve_cluster" in cached:
        sc = cached["serve_cluster"]
        sca = sc.get("scaling", {})
        print(f"serve_cluster.campaign.speedup,"
              f"{sca.get('speedup_4w', float('nan')):.2f},target>=3x")
        wr = sc.get("warm_restart", {})
        print(f"serve_cluster.campaign.restart,"
              f"{wr.get('restart_first_sweep_hit_rate', float('nan')):.2f},"
              f"recovered={wr.get('recovered')};"
              f"stale_served={wr.get('bump_stale_served')}")

    _section("Chaos: device failures, migration-aware recovery, rescale")
    if not args.skip_rl:
        from benchmarks import chaos
        chaos.run(pretrain_iters=12 if quick else 80,
                  full=not quick)      # prints chaos.* CSV lines itself
    if "chaos" in cached:
        ch = cached["chaos"]
        hl = ch.get("headline", {})
        print(f"chaos.campaign.migration_bytes_ratio,"
              f"{hl.get('migration_bytes_ratio', float('nan')):.3f},"
              f"bytes_ok={hl.get('aware_beats_scratch_bytes')};"
              f"mk_ok={hl.get('recovery_within_5pct')};"
              f"lat={hl.get('replan_latency_mean_s', float('nan')):.2f}s")
        sv = ch.get("serve", {})
        print(f"chaos.campaign.stale_served,{sv.get('stale_served', -1)},"
              f"replaced={sv.get('fleet_replaced')};"
              f"rehomed={sv.get('rehomed')}")

    _section("Roofline: dry-run terms per (arch x shape x mesh)")
    try:
        from benchmarks import roofline
        roofline.main()
    except FileNotFoundError:
        print("roofline,SKIPPED,run repro/launch/dryrun.py first")

    _section("Roofline: block-sparse kernels vs dense baselines")
    from benchmarks import roofline as RF
    kern = RF.kernels_section(quick=quick)
    RF.report_kernels(kern)
    if "roofline_kernels" in cached:
        hl = cached["roofline_kernels"].get("headline", {})
        print(f"roofline.kernels.campaign.headline,"
              f"{hl.get('sparse_strictly_smaller_50k', -1)},"
              f"attn50k={hl.get('attn_bytes_ratio_50k', float('nan')):.4f};"
              f"pool50k={hl.get('maxpool_bytes_ratio_50k', float('nan')):.4f}")

    print(f"\n[benchmarks] total wall time: {time.time()-t0:.0f}s")


if __name__ == "__main__":
    from repro.obs.jaxprof import enable_compile_cache
    enable_compile_cache()
    main()
