"""PPO machinery: learns a non-trivial reward; baselines bookkeeping."""
import jax.numpy as jnp
import numpy as np

from repro.core.featurize import featurize
from repro.core.policy import PolicyConfig
from repro.core.ppo import PPOConfig, PPOTrainer, _per_node_advantage
from repro.graphs import synthetic as S
from repro.sim import p100_topology


class FracEnv:
    """Reward = fraction of nodes on device 0 (asymmetric, learnable)."""

    def rewards(self, placements):
        frac = (placements == 0).mean(axis=1).astype(jnp.float32)
        return 1.0 - frac, frac - 1.0, jnp.ones(placements.shape[0], bool)


def test_ppo_learns_trivial_reward():
    g = S.rnnlm(2, time_steps=3)
    gb = featurize(g, max_deg=8, topo=p100_topology(4))
    pcfg = PolicyConfig(hidden=32, gnn_layers=2, placer_layers=1, ffn=64,
                        window=32, max_devices=8)
    tr = PPOTrainer(pcfg, PPOConfig(num_samples=8, lr=3e-3, epochs=2,
                                    entropy_coef=0.005, canonicalize=False,
                                    per_node_credit=True), seed=0)
    m0 = tr.iteration("t", gb, FracEnv(), 4)
    for _ in range(40):
        m = tr.iteration("t", gb, FracEnv(), 4)
    assert m["reward_mean"] > m0["reward_mean"] + 0.3


def test_running_average_baseline():
    g = S.rnnlm(2, time_steps=3)
    gb = featurize(g, max_deg=8, topo=p100_topology(4))
    pcfg = PolicyConfig(hidden=32, gnn_layers=1, placer_layers=1, ffn=64,
                        window=32, max_devices=8)
    tr = PPOTrainer(pcfg, PPOConfig(num_samples=4, epochs=1,
                                    canonicalize=False), seed=0)
    tr.iteration("t", gb, FracEnv(), 4)
    c0 = tr.state.baseline_counts["t"]
    tr.iteration("t", gb, FracEnv(), 4)
    assert tr.state.baseline_counts["t"] == c0 + 4   # all previous trials


def test_per_node_advantage_estimator():
    pl = np.array([[0, 1], [1, 1], [0, 0], [1, 0]])
    r = np.array([1.0, -1.0, 1.0, -1.0])      # node0==0 -> +1
    adv = _per_node_advantage(pl, r, 2, r.copy(), mix=1.0)
    assert adv[0, 0] > 0.5 and adv[1, 0] < -0.5
    np.testing.assert_allclose(adv[:, 1], 0.0, atol=1e-6)


def test_ppo_zero_recompiles_after_first_iteration():
    """Retrace regression pin: iteration 1 traces the sample/update/logp
    programs; iterations 2..N with the same task must add ZERO new jit
    programs (deltas, not absolutes — jit caches persist across tests)."""
    from repro.obs import jaxprof

    g = S.rnnlm(2, time_steps=3)
    gb = featurize(g, max_deg=8, topo=p100_topology(4))
    pcfg = PolicyConfig(hidden=32, gnn_layers=2, placer_layers=1, ffn=64,
                        window=32, max_devices=8)
    tr = PPOTrainer(pcfg, PPOConfig(num_samples=8, epochs=2,
                                    canonicalize=False), seed=0)
    tr.iteration("t", gb, FracEnv(), 4)           # traces everything
    mon = jaxprof.RetraceMonitor()
    for _ in range(3):
        m = tr.iteration("t", gb, FracEnv(), 4)
        assert m["retraces"] == 0                 # per-iteration metric
        assert m["compiles"] == 0                 # every backend compile
        assert m["iter_s"] > 0
        assert np.isfinite(m["clip_frac"]) and np.isfinite(m["approx_kl"])
    assert mon.total_delta() == 0                 # zero new programs total


def test_iteration_spans_split_sampling_and_update():
    """A segmented PPO iteration nests the numpy relabel and the re-score
    in ``ppo.sample`` and the gradient and optimizer dispatch in
    ``ppo.update``; the simulator opens no span of its own."""
    from repro.core.scale import ScaleConfig
    from repro.obs.trace import Tracer, set_tracer
    from repro.sim.scheduler import Env, SimConfig, prepare_sim_graph

    seg = 16
    g = S.rnnlm(2, time_steps=3)
    topo = p100_topology(4).with_mem_caps(g.total_mem() / 4 * 1.8)
    gb = featurize(g, max_deg=8, topo=topo,
                   scale=ScaleConfig(pad_multiple=seg))
    env = Env.from_config(prepare_sim_graph(g, topo, pad_multiple=seg),
                          topo, SimConfig(shaped_reward=True), segment=seg)
    pcfg = PolicyConfig(hidden=32, gnn_layers=2, placer_layers=1, ffn=64,
                        window=32, max_devices=8,
                        scale=ScaleConfig(segment=seg, gnn_chunk=seg))
    tr = PPOTrainer(pcfg, PPOConfig(num_samples=4, epochs=1), seed=0)
    mine = Tracer()
    old = set_tracer(mine)
    try:
        m = tr.iteration("t", gb, env, 4)
    finally:
        set_tracer(old)
    assert isinstance(m["compiles"], int) and m["compiles"] >= 0
    by = {}
    for s in mine.spans:
        by.setdefault(s.name, []).append(s)
    assert "sim.rewards" not in by

    def inside(child, parent):
        return (parent.ts <= child.ts and
                child.ts + child.dur <= parent.ts + parent.dur)

    (sample,), (update,), (sim,) = (by["ppo.sample"], by["ppo.update"],
                                    by["ppo.simulate"])
    for name in ("ppo.relabel", "ppo.logp"):
        (child,) = by[name]
        assert inside(child, sample), name
    for name in ("ppo.update.grad", "ppo.update.optim"):
        (child,) = by[name]
        assert inside(child, update), name
    assert by["ppo.relabel"][0].ts < by["ppo.logp"][0].ts
    assert by["ppo.update.grad"][0].ts < by["ppo.update.optim"][0].ts
    assert sample.ts + sample.dur <= sim.ts <= update.ts


def test_warm_segmented_iteration_dispatches_compiled_programs(monkeypatch):
    """A warm segmented PPO iteration re-scores and updates through the
    compiled programs: the Python body of ``_tf_segment`` is entered only
    while iteration 1 traces them, iteration 2 adds no program and no
    backend compile, and each epoch opens one ``ppo.update.grad`` and one
    ``ppo.update.optim`` inside ``ppo.update``."""
    from repro.core import placer as PL
    from repro.core.scale import ScaleConfig
    from repro.obs.trace import Tracer, set_tracer
    from repro.sim.scheduler import Env, SimConfig, prepare_sim_graph

    entered = []
    real = PL._tf_segment

    def counting(*a, **k):
        entered.append(1)
        return real(*a, **k)
    monkeypatch.setattr(PL, "_tf_segment", counting)

    seg = 16
    g = S.rnnlm(2, time_steps=3)
    topo = p100_topology(4).with_mem_caps(g.total_mem() / 4 * 1.8)
    gb = featurize(g, max_deg=8, topo=topo,
                   scale=ScaleConfig(pad_multiple=seg))
    env = Env.from_config(prepare_sim_graph(g, topo, pad_multiple=seg),
                          topo, SimConfig(shaped_reward=True), segment=seg)
    # a width no other test uses, so iteration 1 traces here
    pcfg = PolicyConfig(hidden=24, gnn_layers=1, placer_layers=1, ffn=48,
                        window=16, max_devices=8,
                        scale=ScaleConfig(segment=seg, gnn_chunk=seg))
    tr = PPOTrainer(pcfg, PPOConfig(num_samples=4, epochs=1), seed=0)
    tr.iteration("t", gb, env, 4)
    assert entered                                # traced in iteration 1
    entered.clear()
    mine = Tracer()
    old = set_tracer(mine)
    try:
        m = tr.iteration("t", gb, env, 4)
    finally:
        set_tracer(old)
    assert entered == []
    assert m["retraces"] == 0 and m["compiles"] == 0
    by = {}
    for s in mine.spans:
        by.setdefault(s.name, []).append(s)
    assert "placer.tf_segment" not in by
    (update,) = by["ppo.update"]
    for name in ("ppo.update.grad", "ppo.update.optim"):
        (child,) = by[name]
        assert (update.ts <= child.ts and
                child.ts + child.dur <= update.ts + update.dur), name
