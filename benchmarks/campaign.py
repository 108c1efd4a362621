"""Long-running benchmark campaign: fills results/experiments.json.

Run in the background; benchmarks/run.py reports these cached numbers
alongside its live quick-mode run.

    PYTHONPATH=src nohup python -m benchmarks.campaign &
"""
from __future__ import annotations

import argparse

from benchmarks import common as C


def main():
    """Run the long campaign section by section, checkpointing
    results/experiments.json after each one."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=250)
    args = ap.parse_args()
    # campaign floor: whatever lands in the cache prints as *.campaign.*
    # (run.py), so --iters must not be able to drive any section below
    # campaign budgets — sections without recorded provenance can't be
    # caught by common.is_campaign_grade afterwards
    iters = max(args.iters, 120)
    if iters != args.iters:
        print(f"[campaign] --iters {args.iters} below campaign floor, "
              f"using {iters}", flush=True)

    from benchmarks import table1_individual, table2_batch, generalization, \
        ablation

    print("[campaign] table1", flush=True)
    C.cache_section("table1", table1_individual.run(iterations=iters),
                    campaign_grade=True)

    print("[campaign] table2", flush=True)
    C.cache_section("table2", table2_batch.run(
        iterations=max(iters // 2, 60)), campaign_grade=True)

    print("[campaign] generalization", flush=True)
    C.cache_section("generalization", generalization.run(
        pretrain_iters=max(iters // 2, 60), finetune_iters=50),
        campaign_grade=True)

    print("[campaign] ablation", flush=True)
    C.cache_section("ablation", ablation.run(
        iterations=max(iters // 3, 50)), campaign_grade=True)

    print("[campaign] hetero", flush=True)
    from benchmarks import hetero
    C.cache_section("hetero", hetero.run(iterations=max(iters // 2, 60),
                                         full=True), campaign_grade=True)

    print("[campaign] transfer", flush=True)
    from benchmarks import transfer
    C.cache_section("transfer", transfer.run(
        pretrain_iters=max(iters // 2, 60), finetune_iters=50,
        full=True), campaign_grade=True)

    print("[campaign] large", flush=True)
    from benchmarks import large_graph
    C.cache_section("large", large_graph.run(
        quick=False, pretrain_iters=max(iters // 4, 40),
        finetune_iters=24), campaign_grade=True)

    print("[campaign] serve", flush=True)
    from benchmarks import serve
    C.cache_section("serve", serve.run(quick=False), campaign_grade=True)

    print("[campaign] serve_cluster", flush=True)
    C.cache_section("serve_cluster", serve.run_cluster(quick=False),
                    campaign_grade=True)

    print("[campaign] chaos", flush=True)
    from benchmarks import chaos
    C.cache_section("chaos", chaos.run(
        pretrain_iters=max(iters // 3, 50), full=True), campaign_grade=True)

    print("[campaign] roofline kernels", flush=True)
    from benchmarks import roofline
    C.cache_section("roofline_kernels", roofline.kernels_section(quick=False),
                    campaign_grade=True)
    print("[campaign] done", flush=True)


if __name__ == "__main__":
    from repro.obs.jaxprof import enable_compile_cache
    enable_compile_cache()
    main()
