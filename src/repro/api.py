"""The one front door for device placement: :func:`place`.

Every placement path the repo grew — zero-shot inference from a
pre-trained policy, per-graph PPO fine-tuning, segment-native decoding
for 10k+-node graphs, and the hierarchical coarsen→place→refine pipeline
for 500k+ nodes — is reachable through one call::

    from repro.api import place
    plan = place(graph, topology, budget=Budget(finetune_iters=40))
    plan.placement      # i32[N] device assignment
    plan.makespan       # simulated seconds under the same SimConfig

Routing is automatic: a :class:`~repro.graphs.shards.GraphShards`
handle, or any graph above ``ScaleConfig.hier_threshold`` nodes, goes
hierarchical; ``budget.finetune_iters == 0`` means zero-shot (best of
``budget.samples`` decodes, no weight updates); everything else is the
paper's per-graph fine-tune.  The policy's ``pcfg.scale`` threads every
size knob (segmented decode, chunked GNN, padding grid, hierarchy
thresholds) through featurizer, policy, simulator, and the hierarchical
pipeline in one object.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Union

import numpy as np

from repro.core.featurize import featurize
from repro.core.graph import DataflowGraph
from repro.core.policy import PolicyConfig
from repro.core.ppo import PPOConfig, PPOTrainer
from repro.graphs.shards import GraphShards, _arrays_digest
from repro.sim.scheduler import Env, SimConfig, prepare_sim_graph

__all__ = ["Budget", "PlacementPlan", "place"]

# default policy for callers that don't bring their own (matches the
# benchmark harness's footprint so pre-trained benchmark checkpoints fit)
DEFAULT_POLICY = PolicyConfig(hidden=64, gnn_layers=2, placer_layers=2,
                              ffn=256, window=64, max_devices=8)
DEFAULT_PPO = PPOConfig(num_samples=32, lr=1e-3, entropy_coef=0.02,
                        entropy_decay=0.99, epochs=2, adv_norm=True,
                        canonicalize=True)


@dataclasses.dataclass(frozen=True)
class Budget:
    """How much search :func:`place` may spend.

    ``pretrain_iters`` only applies when ``pretrain_tasks`` are passed
    (a corpus to train on before touching the target graph);
    ``finetune_iters == 0`` selects zero-shot inference;
    ``refine_windows`` caps how many fine-graph windows the hierarchical
    path re-decodes (``None`` = sweep the whole graph once).
    """
    pretrain_iters: int = 0
    finetune_iters: int = 40
    samples: int = 8
    seed: int = 0
    refine_windows: Optional[int] = None


@dataclasses.dataclass
class PlacementPlan:
    """What :func:`place` hands back: the placement plus its provenance."""
    placement: np.ndarray        # i32[N] device per node
    makespan: float              # simulated seconds (true reward SimConfig)
    valid: bool                  # respects all per-device memory caps
    method: str                  # "zero_shot" | "finetune" | "hierarchical"
    num_devices: int
    # provenance: graph/topology/coarsening content hashes — enough to
    # reproduce or cache the plan (serve.fingerprint semantics)
    fingerprints: Dict[str, str]
    # coarse→refined makespan trace for hierarchical plans; a single
    # entry (the final makespan) otherwise
    trajectory: List[float]
    wall_s: float

    def __post_init__(self):
        self.placement = np.asarray(self.placement, np.int32)


def _fingerprints(graph, topo) -> Dict[str, str]:
    from repro.serve.fingerprint import graph_fingerprint, \
        topology_fingerprint
    fp: Dict[str, str] = {"topology": topology_fingerprint(topo)}
    if isinstance(graph, GraphShards):
        fp["graph"] = graph.digest
    elif graph.num_nodes <= 65536:
        fp["graph"] = graph_fingerprint(graph)
    else:                        # WL refinement is too slow past ~64k
        fp["graph"] = _arrays_digest(graph)
    return fp


def place(graph: Union[DataflowGraph, GraphShards], topology, *,
          budget: Budget = Budget(),
          sim: Optional[SimConfig] = None,
          pcfg: Optional[PolicyConfig] = None,
          ppo: Optional[PPOConfig] = None,
          trainer: Optional[PPOTrainer] = None,
          pretrain_tasks: Optional[List[Any]] = None,
          method: str = "auto", log_every: int = 0) -> PlacementPlan:
    """Place ``graph`` onto ``topology`` and return a :class:`PlacementPlan`.

    ``trainer`` continues from pre-trained weights (e.g. a GDP-batch
    pre-train); otherwise a fresh ``PPOTrainer(pcfg, ppo, budget.seed)``
    is built, optionally pre-trained on ``pretrain_tasks`` (a list of
    ``(name, gb, env, num_devices)`` tuples) for ``budget.pretrain_iters``
    iterations.  ``method`` forces a path ("zero_shot" / "finetune" /
    "hierarchical"); the default ``"auto"`` routes by size.
    """
    t0 = time.perf_counter()
    sim = sim or SimConfig()
    pcfg = pcfg or dataclasses.replace(
        DEFAULT_POLICY, max_devices=max(DEFAULT_POLICY.max_devices,
                                        topology.num_devices))
    sc = pcfg.scale
    ppo = ppo or dataclasses.replace(DEFAULT_PPO,
                                     num_samples=max(budget.samples, 2))
    n = graph.num_nodes

    if method == "auto":
        if isinstance(graph, GraphShards) or n > sc.hier_threshold:
            method = "hierarchical"
        elif budget.finetune_iters <= 0:
            method = "zero_shot"
        else:
            method = "finetune"

    if trainer is None:
        trainer = PPOTrainer(pcfg, ppo, seed=budget.seed)
        if pretrain_tasks and budget.pretrain_iters > 0:
            trainer.train(pretrain_tasks, budget.pretrain_iters,
                          log_every=log_every)

    fps = _fingerprints(graph, topology)

    if method == "hierarchical":
        from repro.hier import place_hierarchical
        res = place_hierarchical(
            graph, topology, pcfg=pcfg, ppo=ppo, sim=sim,
            iterations=budget.finetune_iters, num_samples=budget.samples,
            seed=budget.seed, trainer=trainer,
            max_windows=budget.refine_windows, log_every=log_every)
        fps["coarse"] = res.coarsening.fingerprint
        return PlacementPlan(
            placement=res.placement, makespan=res.makespan, valid=res.valid,
            method="hierarchical", num_devices=topology.num_devices,
            fingerprints=fps, trajectory=res.trajectory,
            wall_s=time.perf_counter() - t0)

    if isinstance(graph, GraphShards):
        graph = graph.load_graph()
    gb = featurize(graph, topo=topology,
                   scale=sc.with_segment_padding())
    sg = prepare_sim_graph(graph, topology, pad_to=gb.op.shape[0],
                           pad_multiple=sc.segment)
    env_true = Env.from_config(sg, topology, sim, segment=sc.segment)
    d = topology.num_devices

    if method == "zero_shot":
        from repro.core.policy import sample as policy_sample
        import jax
        pl, _ = policy_sample(trainer.state.params, pcfg, gb, d,
                              jax.random.PRNGKey(budget.seed),
                              max(budget.samples, 1))
        mks, _, valids = env_true.rewards(pl)
        mks = np.where(np.asarray(valids), np.asarray(mks), np.inf)
        j = int(mks.argmin())
        best = np.asarray(pl[j], np.int32)[:n]
        mk = float(mks[j])
        return PlacementPlan(placement=best, makespan=mk,
                             valid=bool(np.isfinite(mk)),
                             method="zero_shot", num_devices=d,
                             fingerprints=fps, trajectory=[mk],
                             wall_s=time.perf_counter() - t0)

    if method != "finetune":
        raise ValueError(f"place: unknown method {method!r}")
    env_train = Env.from_config(
        sg, topology, dataclasses.replace(sim, shaped_reward=True),
        segment=sc.segment)
    ft = trainer.finetune(graph.name, gb, env_train, d,
                          budget.finetune_iters)
    if ft["best_placement"] is None:
        from repro.core import baselines as B
        best = np.asarray(B.round_robin(graph, topology), np.int32)
    else:
        best = np.asarray(ft["best_placement"], np.int32)
    pad_n = gb.op.shape[0]
    padded = np.zeros(pad_n, np.int32)
    padded[:min(len(best), pad_n)] = best[:pad_n]
    mks, _, valids = env_true.rewards(padded[None])
    mk = float(np.asarray(mks)[0])
    return PlacementPlan(placement=padded[:n], makespan=mk,
                         valid=bool(np.asarray(valids)[0]),
                         method="finetune", num_devices=d,
                         fingerprints=fps, trajectory=[mk],
                         wall_s=time.perf_counter() - t0)
