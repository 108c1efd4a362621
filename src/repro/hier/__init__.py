"""Hierarchical coarsen → place → refine pipeline for 500k+-node graphs.

GDP's policy network scales to tens of thousands of nodes, not
millions: the padded feature/neighbor matrices and the AR decode are
O(N·K) and O(N·W).  This package closes the gap with the classic
multilevel strategy:

1. :func:`~repro.hier.coarsen.coarsen` contracts the fine graph into a
   few-thousand-supernode coarse graph (deterministic, cost-conserving,
   DAG-by-construction);
2. the existing GDP policy trains on and places the *coarse* graph;
3. :func:`~repro.hier.refine.refine` streams the fine graph window by
   window, re-deciding each window with the lifted coarse placement as
   the incumbent (PR 7's migration-bias decode) under full-graph
   simulator acceptance.

Peak RSS is bounded by the coarse graph plus one refinement window plus
the simulator's O(N) scalar arrays — never by O(N·K) fine featurization.
:func:`place_hierarchical` runs the whole pipeline; `repro.api.place`
routes jumbo graphs here automatically.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Union

import jax
import numpy as np

from repro.core import baselines as B
from repro.core.featurize import featurize
from repro.core.graph import DataflowGraph
from repro.core.ppo import PPOConfig, PPOTrainer
from repro.graphs.shards import GraphShards
from repro.hier.coarsen import Coarsening, coarsen
from repro.hier.refine import RefineResult, refine
from repro.sim.scheduler import Env, SimConfig, prepare_sim_graph

__all__ = ["Coarsening", "coarsen", "RefineResult", "refine",
           "HierResult", "place_hierarchical"]


@dataclasses.dataclass
class HierResult:
    """Everything the hierarchical pipeline produced, bottom to top."""
    placement: np.ndarray        # i32[N] final fine placement
    makespan: float              # full-graph makespan of `placement`
    valid: bool                  # respects every per-device memory cap
    coarse_makespan: float       # lifted coarse placement, fine simulator
    trajectory: List[float]      # coarse→refined makespan per window
    coarsening: Coarsening       # fingerprints + partition map
    refine_accepted: int         # windows whose re-placement was taken
    train_iters: int             # PPO iterations spent on the coarse graph
    wall_s: float


def place_hierarchical(source: Union[DataflowGraph, GraphShards], topo, *,
                       pcfg, ppo: Optional[PPOConfig] = None,
                       sim: Optional[SimConfig] = None,
                       iterations: int = 40, num_samples: int = 8,
                       seed: int = 0, trainer: Optional[PPOTrainer] = None,
                       max_windows: Optional[int] = None,
                       log_every: int = 10) -> HierResult:
    """Coarsen ``source``, train/place GDP on the coarse graph, lift, and
    refine window by window.

    ``trainer`` (optional) continues from pre-trained weights instead of
    a fresh ``PPOTrainer(pcfg, ppo, seed)`` — the superposition network
    makes coarse graphs just another graph distribution, so zero-shot +
    short fine-tune works the same as at normal scale.  ``pcfg.scale``
    supplies ``coarse_target`` (supernode count) and ``refine_window``.
    """
    t0 = time.perf_counter()
    sc = pcfg.scale
    sim = sim or SimConfig()
    ppo = ppo or PPOConfig(num_samples=num_samples)
    d = topo.num_devices

    c = coarsen(source, target_nodes=sc.coarse_target)
    coarse = c.coarse
    cgb = featurize(coarse, topo=topo, scale=sc.with_segment_padding())
    csg = prepare_sim_graph(coarse, topo, pad_to=cgb.op.shape[0],
                            pad_multiple=sc.segment)
    cenv = Env.from_config(csg, topo, sim, segment=sc.segment)

    tr = trainer or PPOTrainer(pcfg, ppo, seed=seed)
    ft = tr.finetune(coarse.name, cgb, cenv, d, iterations)
    coarse_pl = ft["best_placement"]
    if coarse_pl is None:
        # no valid sample: start from the memory-balanced greedy baseline
        # and let refinement do the work
        coarse_pl = B.round_robin(coarse, topo)
    coarse_pl = np.asarray(coarse_pl, np.int32)[:coarse.num_nodes]

    fine_g = source.load_graph() if isinstance(source, GraphShards) else source
    fsg = prepare_sim_graph(fine_g, topo)
    fenv = Env.from_config(fsg, topo, sim)
    lifted = c.expand(coarse_pl)

    key = jax.random.PRNGKey(seed + 7)
    rr = refine(tr.state.params, pcfg, fenv, source, topo, lifted, key=key,
                window=sc.refine_window, num_samples=max(num_samples, 2),
                max_windows=max_windows, log_every=log_every)
    _, _, valid = fenv.rewards(rr.placement[None])
    return HierResult(placement=rr.placement, makespan=rr.makespan,
                      valid=bool(np.asarray(valid)[0]),
                      coarse_makespan=rr.trajectory[0],
                      trajectory=rr.trajectory, coarsening=c,
                      refine_accepted=rr.accepted,
                      train_iters=ft["iterations"],
                      wall_s=time.perf_counter() - t0)
