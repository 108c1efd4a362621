"""The benchmark's plain reference agrees with the program at a small
size on the CPU: features, simulator, teacher-forced log-probs (segmented
or not, memory-masked or not, padded or not) and one PPO update."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import harness, weights  # noqa: E402
from chipbench.drivers import common  # noqa: E402
from chipbench.reference import features, policy as ref, sim as ref_sim  # noqa: E402

CFG = harness.load_json(os.path.join(ROOT, "chipbench", "configs",
                                     "gnmt8-p100x8.json"))
SMALL = dict(CFG["policy"], hidden=32, ffn=64, window=16, segment=128,
             gnn_chunk=64)


def _setup(segment, mask_full, fleet=None):
    from repro.core.featurize import featurize
    from repro.graphs import synthetic
    g = synthetic.gnmt(2, time_steps=5)
    fleet = fleet or dict(CFG["fleet"], mem_cap_slack=1.3)
    caps = common.mem_caps(fleet, g)
    topo = common.topology(fleet, caps)
    pol = dict(SMALL, segment=segment, mask_full_devices=mask_full)
    pcfg = common.policy_config(pol)
    gb = featurize(g, topo=topo, scale=pcfg.scale.with_segment_padding())
    fl = features.fleet_arrays(common.reference_fleet(fleet, caps))
    return g, topo, pol, pcfg, gb, fl


def test_features_match_the_programs():
    g, topo, pol, pcfg, gb, fl = _setup(128, True)
    inp = features.policy_inputs(g, fl)
    n = g.num_nodes
    np.testing.assert_allclose(inp["feats"], np.asarray(gb.feats)[:n],
                               rtol=1e-6)
    for k in ("mem_frac", "comp_frac"):
        np.testing.assert_allclose(inp[k], np.asarray(getattr(gb, k))[:n],
                                   rtol=1e-6)
    np.testing.assert_allclose(inp["dev_feats"], np.asarray(gb.dev_feats),
                               rtol=1e-6)
    ours = {tuple(sorted(r[m])) for r, m in zip(inp["nbr_idx"],
                                                  inp["nbr_mask"])}
    prog_idx, prog_m = np.asarray(gb.nbr_idx)[:n], np.asarray(gb.nbr_mask)[:n]
    theirs = {tuple(sorted(r[m > 0])) for r, m in zip(prog_idx, prog_m)}
    assert ours == theirs


def test_simulator_matches_the_programs():
    from repro.sim.scheduler import prepare_sim_graph, simulate_batch, \
        SimTopology
    g, topo, *_ , fl = _setup(None, False)
    rng = np.random.default_rng(0)
    pl = rng.integers(0, topo.num_devices, (5, g.num_nodes)).astype(np.int32)
    pl[0] = np.arange(g.num_nodes) % topo.num_devices
    sg = prepare_sim_graph(g, topo)
    mk, _, valid = simulate_batch(sg, jnp.asarray(pl),
                                  SimTopology.from_topology(topo))
    rmk, _, rvalid = ref_sim.simulate(features.sim_inputs(g, fl), pl)
    np.testing.assert_allclose(np.asarray(mk), rmk, rtol=1e-6)
    assert (np.asarray(valid) == rvalid).all()


@pytest.mark.parametrize("segment,mask_full,pad", [
    (None, False, False), (128, True, False), (None, True, True)])
def test_teacher_forced_logp_matches_the_programs(segment, mask_full, pad):
    from repro.core import policy
    g, topo, pol, pcfg, gb, fl = _setup(segment, mask_full)
    params = weights.make(3, pol)
    n, npad = g.num_nodes, gb.op.shape[0]
    pl = jax.random.randint(jax.random.PRNGKey(1), (3, npad), 0,
                            topo.num_devices)
    with jax.default_matmul_precision("highest"):
        lp, ent = policy.logp_and_entropy(params, pcfg, gb, topo.num_devices,
                                          pl)
    inp = features.policy_inputs(g, fl)
    x = np.asarray(pl)[:, :n]
    if pad:
        inp = features.pad_policy_inputs(inp, npad)
        x = np.asarray(pl)
    with jax.default_matmul_precision("highest"):
        rlp, rent = ref.logp_entropy(params, inp, jnp.asarray(x), policy=pol,
                                     num_devices=topo.num_devices)
    np.testing.assert_allclose(np.asarray(rlp)[:, :n], np.asarray(lp)[:, :n],
                               atol=2e-5)
    if not pad:
        np.testing.assert_allclose(float(rent), float(ent), rtol=1e-5)


def test_one_ppo_update_matches_the_programs():
    from repro.core import ppo as P
    from repro.optim import AdamConfig, adam_init
    g, topo, pol, pcfg, gb, fl = _setup(128, True)
    pp = CFG["ppo"]
    params = weights.make(4, pol)
    n, npad = g.num_nodes, gb.op.shape[0]
    pl = jax.random.randint(jax.random.PRNGKey(2), (4, npad), 0,
                            topo.num_devices)
    adv = np.array([0.5, -1.0, 1.5, -1.0], np.float32)
    ocfg = AdamConfig(lr=pp["lr"])
    with jax.default_matmul_precision("highest"):
        old, _ = P._logp_any(params, pcfg, gb, topo.num_devices, pl)
        p1, o1, aux = P._update_fn(params, adam_init(params, ocfg), pcfg,
                                   ocfg, gb, topo.num_devices, pl, old,
                                   jnp.asarray(adv), pp["clip_eps"],
                                   pp["entropy_coef"], pp["grad_clip"])
        inp = {k: jnp.asarray(v) for k, v in
               features.policy_inputs(g, fl).items()}
        loss, grad, r1, _ = ref.ppo_step(
            params, ref.adam_zeros(params), inp,
            jnp.asarray(np.asarray(pl)[:, :n]), jnp.asarray(adv),
            pp["entropy_coef"], policy=pol, ppo=pp,
            num_devices=topo.num_devices, precision="float32")
    assert abs(float(loss) - float(aux["loss"])) <= 1e-5 * abs(float(loss))
    for a, b in zip(jax.tree_util.tree_leaves(r1),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    mu = jax.tree_util.tree_leaves(o1.mu)
    for a, b in zip(jax.tree_util.tree_leaves(grad), mu):
        np.testing.assert_allclose(np.asarray(a) * 0.1, np.asarray(b),
                                   atol=1e-7)


def test_shaped_reward_and_advantages():
    r = ref_sim.shaped_reward(np.array([4.0, 9.0, 1.0]),
                              np.array([0.5, 1.2, 10.0]))
    np.testing.assert_allclose(r, [-2.0, -4.0, -10.0])
    base = {"count": 0, "value": 0.0}
    r = np.array([-1.0, -2.0, -3.0], np.float32)
    adv = ref.advantages(r, base, True)
    np.testing.assert_allclose(adv.mean(), 0.0, atol=1e-7)
    np.testing.assert_allclose(adv.std(), 1.0, rtol=1e-6)
    assert base == {"count": 3, "value": -2.0}
    ref.advantages(r - 3, base, True)
    assert base == {"count": 6, "value": -3.5}
