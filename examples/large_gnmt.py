"""Place a paper-scale GNMT with the segment-native pipeline.

The paper's headline scalability result places an 8-layer GNMT with over
50k nodes.  This demo runs that pipeline end-to-end: a GDP policy with
segmented attention and chunked GNN featurization (one ``ScaleConfig``
carries both knobs), pre-trained on small graphs, then
superposition-fine-tuned through ``repro.api.place`` on a large held-out
GNMT judged by the segment-batched simulator.

Default is a few-thousand-node GNMT so the demo finishes in minutes;
``--full`` unrolls past 50k nodes (the paper's scale — expect a long
run on CPU).  The full campaign is ``benchmarks/large_graph.py``; for
500k+-node graphs see the hierarchical pipeline (``docs/scaling.md``).

    python examples/large_gnmt.py [--full]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from benchmarks.large_graph import (SEGMENT, SLACK, large_policy,
                                    large_ppo, pretrain_tasks)
from repro.api import Budget, place
from repro.core import baselines as B
from repro.core.ppo import PPOTrainer, clone_state
from repro.graphs import synthetic as S


def main(full: bool = False, pretrain_iters: int = 8,
         finetune_iters: int = 6):
    pcfg = large_policy()
    print(f"segment-native policy: segment={pcfg.scale.segment} "
          f"window={pcfg.window} gnn_chunk={pcfg.scale.gnn_chunk}")

    tasks = pretrain_tasks()
    tr = PPOTrainer(pcfg, large_ppo(num_samples=8), seed=0)
    t0 = time.time()
    tr.train([(t.name, t.gb, t.env, t.num_devices) for t in tasks],
             iterations=pretrain_iters, log_every=0)
    print(f"pre-trained on {[t.name for t in tasks]} "
          f"in {time.time()-t0:.0f}s\n")

    g = S.gnmt(8, time_steps=352 if full else 24)
    print(f"held-out 8-layer GNMT: {g.num_nodes} nodes "
          f"({'paper scale' if full else 'quick demo; --full for >=50k'})")
    task = C.make_task("gnmt-8", g, 8, tighten=SLACK, segment=SEGMENT)
    pad_n = int(task.gb.op.shape[0])
    print(f"padded to {pad_n} nodes = {pad_n // SEGMENT} segments of "
          f"{SEGMENT}; one compiled decode step serves them all")

    for name, fn in (("round-robin", B.round_robin),
                     ("human-expert", B.human_expert)):
        pl = np.zeros(pad_n, np.int32)
        pl[:g.num_nodes] = fn(g, task.topo)
        mk, _, ok = task.env_true.rewards(jnp.asarray(pl)[None])
        print(f"{name:>16s}: {float(mk[0]):.4f}s"
              f"{'' if bool(ok[0]) else '  (OOM -> invalid)'}")

    t1 = time.time()
    zs = place(g, task.topo, pcfg=pcfg, trainer=tr,
               budget=Budget(finetune_iters=0, samples=4))
    print(f"{'GDP zero-shot':>16s}: {zs.makespan:.4f}s  "
          f"({time.time()-t1:.0f}s, no weight updates)")

    t2 = time.time()
    fork = PPOTrainer(pcfg, large_ppo(num_samples=4), seed=7,
                      state=clone_state(tr.state))
    ft = place(g, task.topo, pcfg=pcfg, trainer=fork,
               budget=Budget(finetune_iters=finetune_iters, samples=4))
    print(f"{'GDP fine-tuned':>16s}: {ft.makespan:.4f}s  "
          f"(method={ft.method}, {time.time()-t2:.0f}s)")
    print(f"\npeak RSS: {C.peak_rss_bytes()/2**30:.2f} GiB")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="unroll GNMT past 50k nodes (paper scale)")
    args = ap.parse_args()
    main(full=args.full)
