"""Segment invariance: segmented decode/featurize/simulate == monolithic.

The architecture invariant (docs/architecture.md): segment size NEVER
changes results — only compiled shapes.  These tests pin it bit-for-bit
on small golden graphs across both contention modes and uniform + hetero
topologies, plus the serving-tier jumbo admission/rejection paths.

(The teacher-forced logits are the one tolerance pin: the segmented pass
multiplies [segment, H] blocks where the monolithic pass multiplies one
[N, H] block, and XLA picks its dot kernels by shape, so the two round a
few ULP apart.)
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gnn, placer as PL, policy as P
from repro.core.featurize import featurize, jumbo_bucket
from repro.core.policy import PolicyConfig
from repro.core.ppo import PPOConfig, PPOTrainer
from repro.core.scale import ScaleConfig
from repro.graphs import synthetic as S
from repro.sim import p100_topology
from repro.sim.device import multi_gen_fleet
from repro.sim.scheduler import (Env, SimTopology, prepare_sim_graph,
                                 simulate)
from repro.sim.reference import simulate_ref

CFG = PolicyConfig(hidden=32, gnn_layers=2, placer_layers=2, ffn=64,
                   window=32, max_devices=8)
SEG = 16


def _topos(g):
    return {
        "uniform": p100_topology(4).with_mem_caps(g.total_mem()),
        "hetero": multi_gen_fleet().tightened(g.total_mem()),
    }


@pytest.fixture(scope="module")
def setup():
    g = S.rnnlm(2, time_steps=3)
    topo = p100_topology(4)
    gb = featurize(g, max_deg=8, topo=topo)
    params = P.init(jax.random.PRNGKey(0), CFG)
    return g, gb, params


# ------------------------------------------------------------- AR decode
@pytest.mark.parametrize("seg", [8, 16, 32, 100])
def test_sample_segmented_bitwise(setup, seg):
    """Segmented AR sampling draws the SAME placements with the SAME
    logp as the monolithic scan — same step function, same keys, carried
    state across segment boundaries."""
    _, gb, params = setup
    cfg_seg = dataclasses.replace(
        CFG, scale=ScaleConfig(segment=seg, gnn_chunk=seg))
    key = jax.random.PRNGKey(1)
    pl_m, lp_m = P.sample(params, CFG, gb, 4, key, 3)
    pl_s, lp_s = P.sample(params, cfg_seg, gb, 4, key, 3)
    assert np.array_equal(np.asarray(pl_m), np.asarray(pl_s))
    assert np.array_equal(np.asarray(lp_m), np.asarray(lp_s))


def test_sample_segmented_bitwise_hetero(setup):
    """Same pin with a heterogeneous capability table conditioning the
    decoder head."""
    g, _, params = setup
    topo = multi_gen_fleet().tightened(g.total_mem())
    gb = featurize(g, max_deg=8, topo=topo)
    cfg_seg = dataclasses.replace(CFG, scale=ScaleConfig(segment=SEG))
    key = jax.random.PRNGKey(3)
    pl_m, lp_m = P.sample(params, CFG, gb, topo.num_devices, key, 2)
    pl_s, lp_s = P.sample(params, cfg_seg, gb, topo.num_devices, key, 2)
    assert np.array_equal(np.asarray(pl_m), np.asarray(pl_s))
    assert np.array_equal(np.asarray(lp_m), np.asarray(lp_s))


# ------------------------------------------------------- teacher-forced
@pytest.mark.parametrize("seg", [8, 16, 64])
def test_tf_segmented_bitwise(setup, seg):
    """Segmented teacher-forced logits == jitted monolithic logits to f32
    rounding, for any segment size (the Transformer-XL memory hands each
    node exactly the W-band the banded pass gathers; only the dot
    kernels' accumulation order differs with the block shape)."""
    _, gb, params = setup
    h = gnn.apply(params["gnn"], gb)
    from repro.core import superposition
    c = superposition.gain(params["sp"],
                           gnn.graph_summary(h, gb.node_mask))
    key = jax.random.PRNGKey(2)
    pl, _ = P.sample(params, CFG, gb, 4, key, 1)
    pl = pl[0]
    tf_jit = jax.jit(partial(PL.apply_tf, window=CFG.window,
                             heads=CFG.heads, num_devices=4))
    lg_m = tf_jit(params["placer"], h, gb.node_mask, pl, c, gb.mem_frac,
                  gb.comp_frac, gb.dev_feats)
    lg_s = PL.apply_tf_segmented(params["placer"], h, gb.node_mask, pl, c,
                                 gb.mem_frac, gb.comp_frac, gb.dev_feats,
                                 segment=seg, window=CFG.window,
                                 heads=CFG.heads, num_devices=4)
    np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_m),
                               rtol=1e-6, atol=1e-6)


def _tf_segment_loop(params, h, node_mask, placements, c, mem_frac,
                     comp_frac, dev_feats, *, segment, window, heads,
                     num_devices, attn_impl, cap=None, bias=None):
    """The segmented TF pass as a Python loop that dispatches the
    checkpointed ``_tf_segment`` once per segment (the scan's oracle)."""
    n, hid = h.shape
    pad = (-n) % segment
    h = jnp.pad(h, ((0, pad), (0, 0)))
    node_mask, placements, mem_frac, comp_frac = (
        jnp.pad(a, (0, pad)) for a in (node_mask, placements, mem_frac,
                                       comp_frac))
    if bias is not None:
        bias = jnp.pad(bias, ((0, pad), (0, 0)))
    prev, ctx, mem_before = PL._tf_ctx(params, placements, node_mask,
                                       mem_frac, comp_frac)
    x = PL._inputs(params, h, prev, ctx)
    dev_keys = PL._dev_keys(params, dev_feats)
    mem = jnp.zeros((len(params["layers"]), window - 1, heads, hid // heads))
    kmem = vmem = mem
    step = jax.checkpoint(partial(PL._tf_segment, heads=heads,
                                  num_devices=num_devices,
                                  use_attention=True, attn_impl=attn_impl))
    outs = []
    for s0 in range(0, n + pad, segment):
        sl = slice(s0, s0 + segment)
        logits, kmem, vmem = step(
            params, x[sl], jax.lax.stop_gradient(kmem),
            jax.lax.stop_gradient(vmem), node_mask[sl], jnp.int32(s0), c,
            dev_keys, mem_before[sl], mem_frac[sl], cap,
            None if bias is None else bias[sl])
        outs.append(logits)
    return jnp.concatenate(outs)[:n]


@pytest.mark.parametrize("attn_impl,masked", [("jnp", False), ("jnp", True),
                                               ("pallas_band", False)])
def test_tf_segmented_scan_matches_segment_loop(setup, attn_impl, masked):
    """The scanned segmented TF pass equals a per-segment loop over
    ``_tf_segment``: float32 logits to 1e-6, and the gradient of a scalar
    loss through either to 1e-5 relative on every leaf.  The gradients are
    taken in float64, where the summation order the scan changes no
    longer shows, so what is compared is the algorithm: the carried
    memory, its stop-gradient at each segment boundary, and each
    segment's rematerialized backward."""
    g, gb, params = setup
    h = gnn.apply(params["gnn"], gb)
    from repro.core import superposition
    c = superposition.gain(params["sp"],
                           gnn.graph_summary(h, gb.node_mask))
    pl, _ = P.sample(params, CFG, gb, 4, jax.random.PRNGKey(5), 1)
    pl = pl[0]
    # the init scales the attention output by 1e-2; undo that so the band,
    # the carried memory and their gradients weigh in the logits
    placer = dict(params["placer"], layers=[
        dict(lp, wo=dict(lp["wo"], w=100.0 * lp["wo"]["w"]))
        for lp in params["placer"]["layers"]])
    caps = jnp.full((4,), 0.4) if masked else None
    bias = (0.5 * gb.mem_frac[:, None] * jax.nn.one_hot((pl + 1) % 4,
                                                        CFG.max_devices)
            if masked else None)
    kw = dict(segment=SEG, window=CFG.window, heads=CFG.heads,
              num_devices=4, attn_impl=attn_impl)

    def passes(dtype):
        def cast(a):
            return None if a is None else jnp.asarray(a, dtype)
        args = [cast(a) for a in (h, gb.node_mask)] + [pl] + [
            cast(a) for a in (c, gb.mem_frac, gb.comp_frac, gb.dev_feats)]
        b = cast(bias)

        def scanned(pp):
            return PL.apply_tf_segmented(
                pp, *args, dev_mem_cap=cast(caps), mask_full=masked,
                incumbent_bias=b, **kw)

        def looped(pp):
            cap = PL._cap_vector(pp, cast(caps)) if masked else None
            return _tf_segment_loop(pp, *args, cap=cap, bias=b, **kw)

        return (jax.tree_util.tree_map(cast, placer), scanned, looped)

    pp, scanned, looped = passes(jnp.float32)
    np.testing.assert_allclose(np.asarray(scanned(pp)),
                               np.asarray(looped(pp)), rtol=1e-6, atol=1e-6)

    def loss(tf):
        def f(pp):
            lp = jax.nn.log_softmax(tf(pp), axis=-1)
            node_lp = jnp.take_along_axis(lp, pl[:, None], axis=-1)[:, 0]
            return (node_lp * gb.node_mask).sum() + (jnp.exp(lp) * lp).sum()
        return f

    with jax.enable_x64(True):
        pp, scanned, looped = passes(jnp.float64)
        flat_s = jax.tree_util.tree_leaves_with_path(
            jax.grad(loss(scanned))(pp))
        flat_l = [np.asarray(a) for a in
                  jax.tree_util.tree_leaves(jax.grad(loss(looped))(pp))]
    assert len(flat_s) == len(flat_l)
    assert all(np.asarray(a).dtype == np.float64 for _, a in flat_s)
    # a leaf whose gradient is nought by symmetry (a key or device-key
    # bias under the softmax) reads only rounding: nought in both
    tiny = 1e-9 * float(np.median([np.linalg.norm(b) for b in flat_l]))
    for (path, a), b in zip(flat_s, flat_l):
        name = jax.tree_util.keystr(path)
        a = np.asarray(a)
        if np.linalg.norm(b) < tiny:
            assert np.linalg.norm(a) < tiny, name
            continue
        gap = np.linalg.norm(a - b) / np.linalg.norm(b)
        assert gap <= 1e-5, (name, gap)


def _gather_band(q, kbuf, vbuf, base, window):
    """The band as a gather of each query's W columns, [S, W, heads, hd]
    copies of K and V (the oracle of ``PL._band_attention``)."""
    s, heads, hd = q.shape
    idx = jnp.arange(s)[:, None] + jnp.arange(window)[None, :]
    valid = (base + idx - (window - 1)) >= 0
    kb, vb = kbuf[idx], vbuf[idx]
    sc = jnp.einsum("nhd,nwhd->nhw", q, kb) / jnp.sqrt(
        jnp.asarray(hd, q.dtype))
    sc = jnp.where(valid[:, None, :], sc, PL.NEG)
    return jnp.einsum("nhw,nwhd->nhd", jax.nn.softmax(sc, axis=-1), vb)


@pytest.mark.parametrize("s,window,base,kind", [
    (64, 16, 0, "segment"),             # the first segment of a graph
    (64, 16, 1000, "segment"),          # a segment mid-graph
    (96, 40, 200, "segment"),           # W does not divide S
    (24, 40, 0, "whole"),               # W > n: the monolithic band
    (32, 16, 480, "padding"),           # a segment past the last node
])
def test_band_attention_matches_gather(s, window, base, kind):
    """The blocked-window band equals the gathered band: float32 values
    to 1e-6 and float64 gradients w.r.t. the queries, keys and values to
    1e-5 relative, at segment starts, mid-graph, a W that does not divide
    S, a W wider than the sequence (against the monolithic pass's own
    gather, with W-1 zero columns before the start of time), and a
    segment of padding (zero queries and columns after the carried
    memory)."""
    heads, hd = 4, 8
    t = s if kind == "whole" else window - 1 + s
    ks = jax.random.split(jax.random.PRNGKey(s + window + base), 4)
    q = jax.random.normal(ks[0], (s, heads, hd))
    kv = [jax.random.normal(k, (t, heads, hd)) for k in ks[1:3]]
    r = jax.random.normal(ks[3], (s, heads, hd))
    if kind == "padding":
        q = jnp.zeros_like(q)
        kv = [a.at[window - 1:].set(0.0) for a in kv]
    if kind == "whole":
        w = min(window, s)

        def new(q, k, v):
            zero = jnp.zeros((w - 1,) + k.shape[1:], k.dtype)
            return PL._band_attention(q, jnp.concatenate([zero, k]),
                                      jnp.concatenate([zero, v]), 0, w)
        old = partial(PL._banded_attention, window=window)
    else:
        new = partial(PL._band_attention, base=jnp.int32(base),
                      window=window)
        old = partial(_gather_band, base=jnp.int32(base), window=window)
    np.testing.assert_allclose(np.asarray(new(q, *kv)),
                               np.asarray(old(q, *kv)), rtol=1e-6, atol=1e-6)
    with jax.enable_x64(True):
        args = [jnp.asarray(a, jnp.float64) for a in (q, *kv)]
        r64 = jnp.asarray(r, jnp.float64)
        g_new = jax.grad(lambda *a: (new(*a) * r64).sum(),
                         argnums=(0, 1, 2))(*args)
        g_old = jax.grad(lambda *a: (old(*a) * r64).sum(),
                         argnums=(0, 1, 2))(*args)
    for name, a, b in zip(("q", "k", "v"), g_new, g_old):
        assert a.dtype == jnp.float64, name
        nb = np.linalg.norm(np.asarray(b))
        gap = np.linalg.norm(np.asarray(a) - np.asarray(b))
        assert gap <= 1e-5 * max(nb, 1e-30), (name, gap, nb)


def test_tf_segmented_grad_has_no_band_gather(setup):
    """The compiled gradient of the segmented TF pass, vmapped over two
    samples, holds no scatter and no gather of the [S·W, ...] band.  It is
    taken w.r.t. the node embeddings and the decoder layers: the device
    embedding's lookup (``_inputs``) lies outside the band and has a
    scatter-add of its own, [Dmax+1, H], as its gradient."""
    import re
    _, gb, params = setup
    seg, window = 24, 8
    h = gnn.apply(params["gnn"], gb)
    pl, _ = P.sample(params, CFG, gb, 4, jax.random.PRNGKey(6), 2)

    def loss(layers, h):
        pp = dict(params["placer"], layers=layers)

        def one(p):
            lg = PL.apply_tf_segmented(
                pp, h, gb.node_mask, p, None, gb.mem_frac, gb.comp_frac,
                gb.dev_feats, segment=seg, window=window, heads=CFG.heads,
                num_devices=4)
            return jax.nn.log_softmax(lg, axis=-1).sum()
        return jax.vmap(one)(pl).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params["placer"]["layers"], h).compile().as_text()
    assert "scatter(" not in text
    gathers = [ln for ln in text.splitlines() if " gather(" in ln]
    band = [ln for ln in gathers
            if str(seg * window) in re.findall(r"\d+", ln.split("=")[1]
                                                .split("gather(")[0])]
    assert not band, band[0][:300]


def test_logp_segmented_matches_monolithic(setup):
    """Policy-level PPO ratio path: per-node logp from the segmented TF
    pass equals the monolithic one to float tolerance on real nodes."""
    _, gb, params = setup
    cfg_seg = dataclasses.replace(CFG, scale=ScaleConfig(segment=SEG))
    pl, _ = P.sample(params, CFG, gb, 4, jax.random.PRNGKey(4), 2)
    lp_m, ent_m = P.logp_and_entropy(params, CFG, gb, 4, pl)
    lp_s, ent_s = P.logp_and_entropy(params, cfg_seg, gb, 4, pl)
    np.testing.assert_allclose(np.asarray(lp_m), np.asarray(lp_s),
                               atol=1e-5, rtol=0)
    assert abs(float(ent_m) - float(ent_s)) < 1e-5


# -------------------------------------------------------- featurization
def test_gnn_chunked_bitwise(setup):
    """Chunked neighbor aggregation == one-shot, bit-for-bit, including
    a chunk that does not divide N (internal padding)."""
    _, gb, params = setup
    h0 = gnn.apply(params["gnn"], gb)
    for chunk in (8, 37, 64):
        h1 = gnn.apply(params["gnn"], gb,
                       scale=ScaleConfig(gnn_chunk=chunk))
        assert np.array_equal(np.asarray(h0), np.asarray(h1)), chunk


def test_gnn_chunked_bitwise_pallas(setup):
    """The pallas row-blocked kernel path agrees with its own one-shot
    densified path bit-for-bit (interpret mode on CPU)."""
    _, gb, params = setup
    h0 = gnn.apply(params["gnn"], gb, agg_impl="pallas")
    h1 = gnn.apply(params["gnn"], gb, agg_impl="pallas",
                   scale=ScaleConfig(gnn_chunk=64))
    assert np.array_equal(np.asarray(h0), np.asarray(h1))


def test_featurize_pad_multiple():
    g = S.rnnlm(2, time_steps=3)
    gb = featurize(g, max_deg=8, scale=ScaleConfig(pad_multiple=64))
    assert gb.op.shape[0] % 64 == 0
    assert gb.op.shape[0] >= g.num_nodes
    assert gb.num_nodes == g.num_nodes
    assert jumbo_bucket(50_001, 2048) == 51_200


# ------------------------------------------------------------- simulate
@pytest.mark.parametrize("contention", [False, True])
@pytest.mark.parametrize("fleet", ["uniform", "hetero"])
def test_simulate_segmented_bitwise(contention, fleet):
    """Segment-batched simulate == monolithic simulate, bit-for-bit, and
    both match the numpy oracle — both contention modes, uniform and
    heterogeneous fleets."""
    g = S.gnmt(2, time_steps=4)
    topo = _topos(g)[fleet]
    st = SimTopology.from_topology(topo)
    sg_m = prepare_sim_graph(g, topo, max_deg=16)
    sg_s = prepare_sim_graph(g, topo, max_deg=16, pad_multiple=32)
    assert sg_s.compute_t.shape[0] % 32 == 0
    rng = np.random.RandomState(0)
    for _ in range(3):
        pl = rng.randint(0, topo.num_devices,
                         size=sg_s.compute_t.shape[0]).astype(np.int32)
        mk_m, u_m, v_m = simulate(sg_m, jnp.asarray(pl[:g.num_nodes]), st,
                                  contention)
        mk_s, u_s, v_s = simulate(sg_s, jnp.asarray(pl), st, contention,
                                  segment=32)
        assert float(mk_m) == float(mk_s)
        assert float(u_m) == float(u_s)
        assert bool(v_m) == bool(v_s)
        ref_mk, _, _ = simulate_ref(g, pl[:g.num_nodes], topo,
                                    sender_contention=contention)
        np.testing.assert_allclose(float(mk_s), ref_mk, rtol=1e-5)


@pytest.mark.parametrize("contention", [False, True])
def test_env_segment_threading(contention):
    """Env(segment=...) returns the same rewards as the monolithic env
    over the same padded arrays (the jit wrapper keys on the mode)."""
    g = S.rnnlm(2, time_steps=3)
    topo = p100_topology(4).with_mem_caps(g.total_mem())
    sg = prepare_sim_graph(g, topo, max_deg=16, pad_multiple=16)
    env_m = Env(sg, topo, sender_contention=contention)
    env_s = Env(sg, topo, sender_contention=contention, segment=16)
    rng = np.random.RandomState(1)
    pls = rng.randint(0, 4, size=(4, sg.compute_t.shape[0])).astype(np.int32)
    mk_m, r_m, v_m = env_m.rewards(pls)
    mk_s, r_s, v_s = env_s.rewards(pls)
    assert np.array_equal(np.asarray(mk_m), np.asarray(mk_s))
    assert np.array_equal(np.asarray(r_m), np.asarray(r_s))
    assert np.array_equal(np.asarray(v_m), np.asarray(v_s))


# ----------------------------------------------------- segmented PPO run
def test_segmented_ppo_iteration_runs():
    """A segment-native PPO fine-tune iteration (eager segmented decode,
    scanned TF pass inside the jitted re-score and update) trains
    end-to-end on a segment-padded task and produces finite, valid
    makespans."""
    from benchmarks import common as C
    pcfg = dataclasses.replace(
        CFG, scale=ScaleConfig(segment=SEG, gnn_chunk=SEG))
    ppo = PPOConfig(num_samples=4, epochs=1)
    g = S.rnnlm(2, time_steps=3)
    task = C.make_task("seg-ppo", g, 4, segment=SEG)
    tr = PPOTrainer(pcfg, ppo, seed=0)
    m = tr.iteration(task.name, task.gb, task.env, task.num_devices)
    assert np.isfinite(m["best_makespan"])
    assert m["best_placement"] is not None


# ------------------------------------------------- paper-scale (slow tier)
@pytest.mark.slow
def test_paper_scale_gnmt_end_to_end():
    """The headline claim: an 8-layer GNMT with >=50k nodes runs the full
    pre-train -> superposition fine-tune -> placement pipeline on one
    host, fits a stated peak-memory bound, and beats round_robin."""
    from benchmarks import large_graph as L
    from benchmarks import common as C

    res = L.run(quick=False, pretrain_iters=4, finetune_iters=4,
                num_samples=2, only=["gnmt-8"])
    row = res["graphs"]["gnmt-8"]
    assert row["nodes"] >= 50_000
    assert np.isfinite(row["gdp"])
    assert row["beats_rr"], (row["gdp"], row["round_robin"])
    # stated peak-memory bound for the whole process (segment-native
    # pipeline: compiled shapes and gathers are O(segment), the audited
    # peak is dominated by PPO residuals + XLA arenas)
    assert res["peak_rss_bytes"] < 24 * 2**30, res["peak_rss_bytes"]


# ------------------------------------------------- memory-aware decode
def test_mask_full_devices_feasible_and_exact():
    """Memory-aware decode: on a memory-tight pool where unconstrained
    sampling from an untrained policy is (almost) never valid, masked
    sampling is feasible by construction; the TF pass applies the same
    mask so AR and TF logp agree; and the segmented masked decode equals
    the monolithic masked decode bit-for-bit."""
    from repro.sim.scheduler import Env as _Env
    g = S.rnnlm(2, time_steps=4)
    topo = p100_topology(4).with_mem_caps(g.total_mem() / 4 * 1.3)
    gb = featurize(g, max_deg=8, topo=topo)
    params = P.init(jax.random.PRNGKey(0), CFG)
    env = _Env(prepare_sim_graph(g, topo, max_deg=16), topo)

    cfg_m = dataclasses.replace(CFG, mask_full_devices=True)
    pl_m, lp_m = P.sample(params, cfg_m, gb, 4, jax.random.PRNGKey(1), 16)
    _, _, valid = env.rewards(pl_m)
    assert bool(np.asarray(valid).all())          # feasible by construction

    lp_tf, _ = P.logp_and_entropy(params, cfg_m, gb, 4, pl_m)
    assert float(jnp.abs(lp_m - lp_tf).max()) < 1e-4   # exact PPO ratios

    cfg_ms = dataclasses.replace(cfg_m, scale=ScaleConfig(segment=SEG))
    pl_s, lp_s = P.sample(params, cfg_ms, gb, 4, jax.random.PRNGKey(1), 16)
    assert np.array_equal(np.asarray(pl_m), np.asarray(pl_s))
    assert np.array_equal(np.asarray(lp_m), np.asarray(lp_s))


def test_mask_off_is_default_distribution():
    """The flag defaults off and off-mode sampling is untouched by the
    dev_mem_cap plumbing (same placements as before the field existed —
    the golden-pin guarantee)."""
    g = S.rnnlm(2, time_steps=3)
    topo = p100_topology(4)
    gb = featurize(g, max_deg=8, topo=topo)
    params = P.init(jax.random.PRNGKey(0), CFG)
    assert CFG.mask_full_devices is False
    assert gb.dev_mem_cap.shape == (4,)
    pl_a, _ = P.sample(params, CFG, gb, 4, jax.random.PRNGKey(2), 2)
    # a batch whose caps are zeroed-out must sample identically when the
    # flag is off (the cap table is dead weight unless enabled)
    gb_z = gb._replace(dev_mem_cap=jnp.zeros(0))
    pl_b, _ = P.sample(params, CFG, gb_z, 4, jax.random.PRNGKey(2), 2)
    assert np.array_equal(np.asarray(pl_a), np.asarray(pl_b))
