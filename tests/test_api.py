"""repro.api.place: one front door, three routes, stable plan contract.

Also pins the ScaleConfig consolidation: ``PolicyConfig.scale`` is the
one place a graph-size knob is set (the old per-config keywords are
rejected), and ``with_segment_padding`` keeps featurizer and simulator
on the same padding grid.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.api import Budget, PlacementPlan, place
from repro.core import gnn
from repro.core.featurize import featurize
from repro.core.policy import PolicyConfig
from repro.core.scale import ScaleConfig
from repro.graphs import synthetic as S
from repro.graphs.shards import write_shards
from repro.serve.service import ServeConfig
from repro.sim import p100_topology

SMALL = PolicyConfig(hidden=16, gnn_layers=1, op_emb=8, placer_layers=1,
                     heads=2, ffn=32, window=16, max_devices=4)


def _setup(d=4, slack=2.5):
    g = S.rnnlm(2, time_steps=6)
    topo = p100_topology(d).with_mem_caps(g.total_mem() / d * slack)
    return g, topo


def _check_plan(plan, g, topo, method):
    assert isinstance(plan, PlacementPlan)
    assert plan.method == method
    assert plan.placement.shape == (g.num_nodes,)
    assert plan.placement.dtype == np.int32
    assert np.all((plan.placement >= 0)
                  & (plan.placement < topo.num_devices))
    assert plan.num_devices == topo.num_devices
    assert plan.makespan > 0 and plan.valid
    assert plan.trajectory and plan.trajectory[-1] == plan.makespan
    # provenance: enough hashes to reproduce/cache the plan
    assert set(plan.fingerprints) >= {"graph", "topology"}
    assert plan.wall_s > 0


def test_place_finetune_default_route():
    g, topo = _setup()
    plan = place(g, topo, pcfg=SMALL,
                 budget=Budget(finetune_iters=2, samples=2))
    _check_plan(plan, g, topo, "finetune")


def test_place_zero_shot_route():
    g, topo = _setup()
    plan = place(g, topo, pcfg=SMALL,
                 budget=Budget(finetune_iters=0, samples=4))
    _check_plan(plan, g, topo, "zero_shot")


def test_place_hierarchical_forced_and_by_threshold():
    g, topo = _setup()
    sc = ScaleConfig(coarse_target=24, refine_window=64)
    plan = place(g, topo, pcfg=dataclasses.replace(SMALL, scale=sc),
                 method="hierarchical",
                 budget=Budget(finetune_iters=2, samples=2))
    _check_plan(plan, g, topo, "hierarchical")
    assert "coarse" in plan.fingerprints
    # coarse+refine <= coarse-only (the monotone contract, through the
    # facade)
    assert plan.makespan <= plan.trajectory[0]
    # auto-routing: a graph above hier_threshold goes hierarchical
    auto = place(g, topo, pcfg=dataclasses.replace(
                     SMALL, scale=dataclasses.replace(sc, hier_threshold=16)),
                 budget=Budget(finetune_iters=2, samples=2))
    assert auto.method == "hierarchical"


def test_place_shards_route_hierarchical(tmp_path):
    g, topo = _setup()
    sh = write_shards(g, str(tmp_path / "sh"), shard_nodes=64)
    sc = ScaleConfig(coarse_target=24, refine_window=64)
    plan = place(sh, topo, pcfg=dataclasses.replace(SMALL, scale=sc),
                 budget=Budget(finetune_iters=2, samples=2,
                               refine_windows=1))
    _check_plan(plan, sh.load_graph(), topo, "hierarchical")
    assert plan.fingerprints["graph"] == sh.digest


def test_place_unknown_method_raises():
    g, topo = _setup()
    with pytest.raises(ValueError, match="unknown method"):
        place(g, topo, pcfg=SMALL, method="simulated_annealing")


# ---------------------------------------------------------------------------
# ScaleConfig consolidation: one spelling
# ---------------------------------------------------------------------------
def _gnn_apply_chunk():
    gb = featurize(S.rnnlm(2, time_steps=3))
    gnn.apply(gnn.init(jax.random.PRNGKey(0), 16, 1), gb, chunk=64)


@pytest.mark.parametrize("call", [
    lambda: PolicyConfig(segment=8),
    lambda: PolicyConfig(gnn_chunk=32),
    lambda: ServeConfig(jumbo_threshold=64),
    lambda: ServeConfig(scale=ScaleConfig()),
    lambda: featurize(S.rnnlm(2, time_steps=3), pad_multiple=64),
    _gnn_apply_chunk,
], ids=["PolicyConfig.segment", "PolicyConfig.gnn_chunk",
        "ServeConfig.jumbo_threshold", "ServeConfig.scale",
        "featurize.pad_multiple", "gnn.apply.chunk"])
def test_removed_scale_keywords_are_rejected(call):
    with pytest.raises(TypeError, match="unexpected keyword"):
        call()


def test_with_segment_padding():
    sc = ScaleConfig(segment=128)
    assert sc.with_segment_padding().pad_multiple == 128
    # explicit pad_multiple and unsegmented configs pass through untouched
    sc2 = ScaleConfig(segment=128, pad_multiple=64)
    assert sc2.with_segment_padding() is sc2
    sc3 = ScaleConfig()
    assert sc3.with_segment_padding() is sc3
