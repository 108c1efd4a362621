"""Table 1: GDP-one vs human expert / METIS / HDP per graph.

Reports, per workload: best placement runtime found by each method, GDP's
speedup over HP and HDP, and the search-time speedup (time for GDP to reach
HDP's final quality vs HDP's search time) — the paper's three Table-1
columns.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks import common as C


def run(iterations: int = 80, tasks=None, seeds=(0,)) -> Dict:
    """Table 1 rows: GDP-one vs HP/METIS/HDP per workload."""
    tasks = tasks or C.paper_tasks()
    rows = {}
    for task in tasks:
        base = C.baseline_rows(task)
        gdp = C.run_gdp_one(task, iterations, seed=seeds[0])
        hdp = C.run_hdp(task, iterations)
        hdp_curve = [(h["elapsed_s"], h["best_makespan"])
                     for h in hdp["history"]]
        t_gdp = C.time_to_quality(gdp["curve"], hdp["best"])
        row = {
            "nodes": task.graph.num_nodes,
            "devices": task.num_devices,
            "gdp_one": gdp["best"],
            "human": base["human"],
            "metis": base["metis"],
            "single": base["single"],
            "random": base["random"],
            "hdp": hdp["best"],
            # inf baseline == the heuristic OOMed (paper's "OOM" rows)
            "speedup_vs_hp": ((base["human"] - gdp["best"]) / base["human"]
                              if np.isfinite(base["human"]) else float("inf")),
            "speedup_vs_hdp": ((hdp["best"] - gdp["best"]) / hdp["best"]
                               if np.isfinite(hdp["best"]) else float("inf")),
            "gdp_search_s": gdp["search_s"],
            "hdp_search_s": hdp["search_s"],
            "search_speedup_vs_hdp": (
                hdp["search_s"] / t_gdp if t_gdp not in (0.0, float("inf"))
                else float("nan")),
        }
        rows[task.name] = row
        print(f"[table1] {task.name:>18s} GDP={row['gdp_one']:.4f} "
              f"HP={row['human']:.4f} METIS={row['metis']:.4f} "
              f"HDP={row['hdp']:.4f} "
              f"dHP={row['speedup_vs_hp']*100:+.1f}% "
              f"dHDP={row['speedup_vs_hdp']*100:+.1f}%", flush=True)
    return rows


def main(quick: bool = True):
    """Run the Table-1 campaign; full-budget runs only are cached."""
    rows = run(iterations=60 if quick else 400)
    C.cache_section("table1", rows, campaign_grade=not quick)
    return rows


if __name__ == "__main__":
    from repro.obs.jaxprof import enable_compile_cache
    enable_compile_cache()
    main(quick=False)
