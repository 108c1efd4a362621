"""Fig. 2: pre-train on a graph set, hold one out; zero-shot + <=50-step
fine-tune on the held-out graph vs training from scratch."""
from __future__ import annotations

import copy
import time
from typing import Dict

import numpy as np

from benchmarks import common as C
from repro.core.ppo import PPOTrainer


def run(pretrain_iters: int = 60, finetune_iters: int = 50, tasks=None) -> Dict:
    """Leave-one-out generalization over ``tasks`` (Fig. 2 protocol)."""
    tasks = tasks or C.paper_tasks()[:4]
    rows = {}
    for held_out in tasks:
        rest = [t for t in tasks if t.name != held_out.name]
        tr = PPOTrainer(C.POLICY, C.PPO, seed=0)
        tr.train([(t.name, t.gb, t.env, t.num_devices) for t in rest],
                 iterations=pretrain_iters, log_every=0)
        # zero-shot: sample from the pre-trained policy, no updates
        zs = tr.best_of_samples(held_out.gb, held_out.env_true,
                                held_out.num_devices, 16)
        # fine-tune <= 50 steps (paper: "fewer than 50 steps, <1 minute")
        t0 = time.time()
        best_ft = np.inf
        for _ in range(finetune_iters):
            m = tr.iteration(held_out.name, held_out.gb, held_out.env,
                             held_out.num_devices)
            best_ft = min(best_ft, m["best_makespan"])
        ft_s = time.time() - t0
        best_ft = min(best_ft, tr.best_of_samples(
            held_out.gb, held_out.env_true, held_out.num_devices, 16))
        base = C.baseline_rows(held_out)
        rows[held_out.name] = {
            "zero_shot": float(zs), "finetune": float(best_ft),
            "finetune_s": ft_s, "human": base["human"],
        }
        print(f"[gen] holdout={held_out.name:>18s} zs={zs:.4f} "
              f"ft={best_ft:.4f} hp={base['human']:.4f} "
              f"({ft_s:.0f}s fine-tune)", flush=True)
    return rows


def main(quick: bool = True):
    """Run the generalization campaign; full-budget runs only are
    cached."""
    rows = run(pretrain_iters=30 if quick else 200,
               finetune_iters=20 if quick else 50)
    C.cache_section("generalization", rows, campaign_grade=not quick)
    return rows


if __name__ == "__main__":
    from repro.obs.jaxprof import enable_compile_cache
    enable_compile_cache()
    main(quick=False)
