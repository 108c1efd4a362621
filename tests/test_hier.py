"""Hierarchical coarsen→place→refine: the pipeline's safety contract.

Pins the four properties ISSUE 10 promises:
  * coarsening conserves total flops / memory / cross-partition bytes;
  * refinement never violates per-device memory caps (structural: caps
    are reduced by outside-window residency before the decode);
  * coarse+refine makespan is monotonically <= coarse-only makespan
    (accept-only-if-strictly-better);
  * the streamed (out-of-core) featurization path is bit-identical to
    the in-RAM featurizer on small graphs.
"""
import dataclasses

import jax
import numpy as np

from repro.core import baselines as B
from repro.core import policy
from repro.core.featurize import featurize, featurize_window
from repro.core.policy import PolicyConfig
from repro.core.scale import ScaleConfig
from repro.graphs import synthetic as S
from repro.graphs.shards import open_shards, write_shards
from repro.hier import coarsen, place_hierarchical, refine
from repro.sim import p100_topology, prepare_sim_graph
from repro.sim.scheduler import Env, SimConfig

SMALL = PolicyConfig(hidden=16, gnn_layers=1, op_emb=8, placer_layers=1,
                     heads=2, ffn=32, window=16, max_devices=4)


def _graph():
    return S.gnmt(2, time_steps=6)


def _topo(g, d=4, slack=2.5):
    return p100_topology(d).with_mem_caps(g.total_mem() / d * slack)


# ---------------------------------------------------------------------------
# coarsening
# ---------------------------------------------------------------------------
def test_coarsen_conserves_costs():
    g = _graph()
    c = coarsen(g, target_nodes=16)
    assert c.coarse.num_nodes == 16
    np.testing.assert_allclose(c.coarse.total_flops(), g.total_flops(),
                               rtol=1e-12)
    np.testing.assert_allclose(c.coarse.mem_bytes.sum(), g.mem_bytes.sum(),
                               rtol=1e-12)
    # every fine byte that crosses a partition boundary lands in exactly
    # one aggregated coarse edge
    w = g.out_bytes[g.src].astype(np.float64)
    cross = c.part[g.src] != c.part[g.dst]
    np.testing.assert_allclose(c.edge_bytes.sum(), w[cross].sum(),
                               rtol=1e-12)


def test_coarsen_partitions_are_contiguous_and_cover():
    g = _graph()
    c = coarsen(g, target_nodes=8)
    assert c.starts[0] == 0 and c.starts[-1] == g.num_nodes
    assert np.all(np.diff(c.starts) >= 1)
    # part is the step function of starts; expand() inverts it
    for p in range(c.num_partitions):
        lo, hi = c.window(p)
        assert np.all(c.part[lo:hi] == p)
    cp = np.arange(c.num_partitions, dtype=np.int32) % 3
    lifted = c.expand(cp)
    assert lifted.shape == (g.num_nodes,)
    assert np.array_equal(lifted, cp[c.part])


def test_coarsen_deterministic_and_shards_equal_inram(tmp_path):
    g = _graph()
    c1 = coarsen(g, target_nodes=16)
    c2 = coarsen(g, target_nodes=16)
    assert c1.fingerprint == c2.fingerprint
    # a different contraction is a different provenance key
    assert coarsen(g, target_nodes=8).fingerprint != c1.fingerprint
    # the shard-backed path must produce the identical coarsening
    sh = write_shards(g, str(tmp_path / "sh"), shard_nodes=64)
    c3 = coarsen(sh, target_nodes=16)
    assert c3.fingerprint == c1.fingerprint
    assert np.array_equal(c3.part, c1.part)


# ---------------------------------------------------------------------------
# streamed featurization == in-RAM featurization
# ---------------------------------------------------------------------------
def test_featurize_window_bit_identical_to_inram(tmp_path):
    g0 = _graph()
    sh = write_shards(g0, str(tmp_path / "sh"), shard_nodes=64)
    g = sh.load_graph()          # canonical (dst, src)-sorted edge order
    topo = _topo(g)
    ref = featurize(g, max_deg=8, topo=topo)
    got = featurize_window(sh, 0, g.num_nodes, max_deg=8, topo=topo)
    for field in ("op", "feats", "nbr_idx", "nbr_mask", "node_mask",
                  "mem_frac", "comp_frac", "dev_feats", "dev_mem_cap"):
        a, b = np.asarray(getattr(ref, field)), np.asarray(getattr(got, field))
        assert a.dtype == b.dtype and a.shape == b.shape, field
        assert np.array_equal(a, b), field
    assert got.num_nodes == ref.num_nodes


def test_featurize_window_masks_out_of_window_neighbors(tmp_path):
    g0 = _graph()
    sh = write_shards(g0, str(tmp_path / "sh"), shard_nodes=64)
    topo = _topo(sh.load_graph())
    lo, hi, pad = 32, 96, 128
    gb = featurize_window(sh, lo, hi, max_deg=8, topo=topo, pad_to=pad)
    assert gb.op.shape[0] == pad and gb.num_nodes == hi - lo
    idx = np.asarray(gb.nbr_idx)
    mask = np.asarray(gb.nbr_mask)
    # every unmasked neighbor is a window-local index; masked slots point
    # at the sentinel row
    assert np.all(idx[mask > 0] < hi - lo)
    assert np.all(idx[mask == 0] == pad)


# ---------------------------------------------------------------------------
# refinement
# ---------------------------------------------------------------------------
def test_refine_monotone_and_cap_safe():
    g = _graph()
    topo = _topo(g)
    env = Env.from_config(prepare_sim_graph(g, topo), topo, SimConfig())
    params = policy.init(jax.random.PRNGKey(0), SMALL)
    start = np.asarray(B.round_robin(g, topo), np.int32)
    mk0, _, ok0 = env.rewards(start[None])
    assert bool(ok0[0])

    res = refine(params, SMALL, env, g, topo, start,
                 key=jax.random.PRNGKey(1), window=64, num_samples=2)
    traj = np.asarray(res.trajectory)
    assert traj[0] == float(mk0[0])
    # accept-only-if-strictly-better => nonincreasing, ends at makespan
    assert np.all(np.diff(traj) <= 0)
    assert res.makespan == traj[-1] <= traj[0]
    # final placement is cap-safe on every device
    usage = np.bincount(res.placement, weights=g.mem_bytes,
                        minlength=topo.num_devices)
    assert np.all(usage <= topo.mem_caps + 1e-6)
    _, _, ok = env.rewards(res.placement[None])
    assert bool(ok[0])


def test_refine_max_windows_bounds_sweep():
    g = _graph()
    topo = _topo(g)
    env = Env.from_config(prepare_sim_graph(g, topo), topo, SimConfig())
    params = policy.init(jax.random.PRNGKey(0), SMALL)
    start = np.asarray(B.round_robin(g, topo), np.int32)
    res = refine(params, SMALL, env, g, topo, start,
                 key=jax.random.PRNGKey(1), window=64, num_samples=2,
                 max_windows=1)
    assert res.windows == 1
    assert len(res.trajectory) == 2


# ---------------------------------------------------------------------------
# full pipeline
# ---------------------------------------------------------------------------
def test_place_hierarchical_end_to_end(tmp_path):
    g = _graph()
    topo = _topo(g)
    sc = ScaleConfig(coarse_target=24, refine_window=64)
    pcfg = dataclasses.replace(SMALL, scale=sc)
    res = place_hierarchical(g, topo, pcfg=pcfg,
                             iterations=2, num_samples=2, seed=0,
                             log_every=0)
    assert res.valid
    assert res.placement.shape == (g.num_nodes,)
    assert res.placement.dtype == np.int32
    # coarse+refine <= coarse-only, and the trajectory records the path
    assert res.makespan <= res.trajectory[0]
    assert res.trajectory[-1] == res.makespan
    assert res.coarsening.num_partitions <= 24
    assert len(res.coarsening.fingerprint) == 64
    # shard-backed source takes the same pipeline to the same contract
    sh = write_shards(g, str(tmp_path / "sh"), shard_nodes=64)
    res2 = place_hierarchical(sh, topo, pcfg=pcfg,
                              iterations=2, num_samples=2, seed=0,
                              log_every=0)
    assert res2.valid and res2.makespan <= res2.trajectory[0]
    assert res2.coarsening.fingerprint == res.coarsening.fingerprint
