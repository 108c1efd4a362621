"""Heterogeneous-fleet campaign: placement on mixed-speed device pools.

Three scenarios the homogeneous paper setup cannot express:

* ``fleet``   — multi-generation GPU fleet (2 fast A100 + 2 slow P100,
  NVLink islands bridged over PCIe): the speed-aware placers must load the
  fast island harder.
* ``cpu_gpu`` — 3 GPUs + 1 big-memory CPU host (Mirhoseini et al. 2017
  setting): the CPU is a memory refuge but a compute trap.
* ``hier``    — 8 uniform GPUs but a non-uniform interconnect (NVLink
  island / PCIe / IB hierarchy, Placeto setting): communication-aware
  placement without speed asymmetry.

Per scenario we report the topology-blind ``round_robin`` control, the
throughput-aware heuristics, and a short GDP search whose decoder is
conditioned on the device-capability table.  The headline check (also a
tier-1 test, marked slow): on mixed-speed pools the trained/greedy placer
beats round-robin outright.
"""
from __future__ import annotations

from typing import Dict

from benchmarks import common as C
from repro.core import baselines as B
from repro.graphs import synthetic as S
from repro.sim.device import (A100, P100, cpu_gpu_topology, multi_gen_fleet,
                              nvlink_host_ib_topology)


def hetero_tasks(full: bool = False):
    """The three mixed-fleet scenarios as memory-tightened Tasks."""
    ts = 12 if full else 5
    fleet = multi_gen_fleet(((A100, 2), (P100, 2)))
    cpu_gpu = cpu_gpu_topology(num_gpus=3, num_cpus=1)
    hier = nvlink_host_ib_topology(num_hosts=2, gpus_per_host=4,
                                   spec=P100, island=2, nvlink_bw=100e9)
    gs = {
        "fleet": S.transformer_xl(2, segments=3 if full else 2),
        "cpu_gpu": S.rnnlm(2, time_steps=ts),
        "hier": S.inception(modules=9 if full else 5),
    }
    topos = {"fleet": fleet, "cpu_gpu": cpu_gpu, "hier": hier}
    tasks = []
    for name, g in gs.items():
        # proportional tightening with a feasibility floor — see
        # Topology.tightened (keeps CPU >> GPU memory, baselines lose on
        # speed rather than OOM)
        tasks.append(C.make_task_topo(
            f"het-{name}", g, topos[name].tightened(g.total_mem())))
    return tasks


def run(iterations: int = 60, full: bool = False, seeds=(0,)) -> Dict:
    """GDP vs baselines on every hetero scenario; returns report rows."""
    rows = {}
    for task in hetero_tasks(full=full):
        base = C.baseline_rows(task)
        gdp = C.run_gdp_one(task, iterations, seed=seeds[0])
        rr = base["round_robin"]
        d_rr, _ = C.vs_baseline(gdp["best"], rr)
        row = {
            "nodes": task.graph.num_nodes,
            "devices": task.num_devices,
            "specs": [s.name for s in task.topo.specs],
            "gdp": gdp["best"],
            "round_robin": rr,
            "human": base["human"],
            "metis": base["metis"],
            "random": base["random"],
            "gdp_vs_round_robin": d_rr,   # None when round_robin OOMs
            "search_s": gdp["search_s"],
        }
        rows[task.name] = row
        print(f"[hetero] {task.name:>12s} GDP={row['gdp']:.4f} "
              f"RR={row['round_robin']:.4f} HP={row['human']:.4f} "
              f"METIS={row['metis']:.4f} "
              f"dRR={C.fmt_pct(d_rr)}", flush=True)
    return rows


def uniform_equivalence_row() -> Dict:
    """Sanity row for the report: Topology.uniform reproduces the
    homogeneous pipeline exactly (same expert placement, same makespan —
    the bit-level pin lives in tests/test_hetero.py)."""
    task = C.make_task("uniform-check", S.rnnlm(2, time_steps=6), 2)
    mk, valid = C.eval_placement(task, B.human_expert(task.graph, task.topo))
    return {"makespan": mk, "valid": valid}


def main(quick: bool = True):
    """Run the hetero campaign; only full-budget runs are cached into
    experiments.json (quick numbers must not surface as campaign)."""
    rows = run(iterations=40 if quick else 300, full=not quick)
    C.cache_section("hetero", rows, campaign_grade=not quick)


if __name__ == "__main__":
    from repro.obs.jaxprof import enable_compile_cache
    enable_compile_cache()
    main()
