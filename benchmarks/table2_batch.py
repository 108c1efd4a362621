"""Table 2/3: GDP-batch (one shared policy, Eq. 1) vs GDP-one."""
from __future__ import annotations

from typing import Dict

from benchmarks import common as C
from repro.core.ppo import PPOTrainer


def run(iterations: int = 60, tasks=None) -> Dict:
    """Table 2 rows: one shared GDP-batch policy vs per-graph GDP-one."""
    tasks = tasks or C.paper_tasks()[:4]
    # GDP-batch: one trainer, round-robin over the task set (Eq. 1)
    tr = PPOTrainer(C.POLICY, C.PPO, seed=0)
    task_tuples = [(t.name, t.gb, t.env, t.num_devices) for t in tasks]
    tr.train(task_tuples, iterations=iterations, log_every=0)
    rows = {}
    for t in tasks:
        batch_best = tr.best_of_samples(t.gb, t.env_true, t.num_devices, 16)
        one = C.run_gdp_one(t, iterations)
        rows[t.name] = {
            "gdp_batch": float(batch_best),
            "gdp_one": one["best"],
            "batch_speedup": (one["best"] - batch_best) / one["best"],
        }
        print(f"[table2] {t.name:>18s} batch={batch_best:.4f} "
              f"one={one['best']:.4f} "
              f"d={rows[t.name]['batch_speedup']*100:+.1f}%", flush=True)
    return rows


def main(quick: bool = True):
    """Run the Table-2 campaign; full-budget runs only are cached."""
    rows = run(iterations=40 if quick else 300)
    C.cache_section("table2", rows, campaign_grade=not quick)
    return rows


if __name__ == "__main__":
    from repro.obs.jaxprof import enable_compile_cache
    enable_compile_cache()
    main(quick=False)
