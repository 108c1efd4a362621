"""Nemotron-3-Nano (Nemotron-H) in the model zoo against its plain reference.

The reduced config keeps the published period ``MEMEM*E`` (Mamba-2, MoE,
attention, each a layer of its own) at tiny widths; ``tests/ref_nemotron_h.py``
computes the same logits as a per-step Mamba-2 recurrence, per-token MoE and
dense causal GQA in float32.  The program's chunked SSD, capacity-based
dispatch and chunked attention must agree with it, and leaving out the gated
norm or the shared expert must not.  At the published widths the one-period
training-step graph is extracted from shapes alone and its matrix-product
FLOPs are held to counts written from the config.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_reduced
from repro.core.graph import op_id
from repro.models import ssm
from repro.models.model import build_model

import ref_nemotron_h as ref

ARCH = "nemotron-3-nano-30b-a3b"
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# Chunked SSD against the sequential recurrence, one-hot dispatch against
# per-token gathers, online softmax against a dense one: the same float32
# sums in other orders.  Seven layers of them leave a few 1e-6 of the
# largest logit (measured 1.1e-6 to 1.8e-6 over 3 seeds); 1e-4 keeps 50x
# of room, stays far under what a missing gated norm or shared expert moves
# (1.2 to 1.5), and under bfloat16 rounding (~1e-2).
REL_TOL = 1e-4


def _cfg():
    cfg = get_reduced(ARCH)
    # capacity for every routed token (k slots each), so nothing drops and
    # the reference's per-token top-k is the same mathematics
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.num_experts)))


def _params(cfg, seed=0):
    """Seeded random weights; the leaves the model starts at 0 or 1 (norm
    scales, biases, dt and decay) are moved off them so each one counts."""
    params = build_model(cfg).init_params(jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    leaves = [a + 0.3 * jax.random.normal(k, a.shape, a.dtype)
              for a, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, leaves)


def _tokens(cfg, b=2, s=32, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, cfg.vocab,
                                                           (b, s)), jnp.int32)


def _train_logits(cfg, params, tok):
    model = build_model(cfg)
    b, s = tok.shape
    x = model._embed_tokens(params, {"tokens": tok})
    h, _, _ = model.backbone(params, x, model._positions({}, s, b),
                             mode="train")
    return h @ model.head(params).T


def _rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _no_gated_norm(monkeypatch, params):
    monkeypatch.setattr(ssm, "gated_rmsnorm",
                        lambda scale, y, z, groups, eps: y * jax.nn.silu(z))
    return params


def _no_shared_expert(monkeypatch, params):
    def drop(path, a):
        names = [getattr(k, "key", None) for k in path]
        return a * 0 if "shared" in names and names[-1] == "w_down" else a
    return jax.tree_util.tree_map_with_path(drop, params)


@pytest.mark.parametrize("fault", [None, _no_gated_norm, _no_shared_expert])
def test_reduced_logits_match_the_reference(fault, monkeypatch):
    cfg = _cfg()
    params = _params(cfg)
    tok = _tokens(cfg)
    want = ref.logits(params, tok, cfg)
    prog = fault(monkeypatch, params) if fault else params
    err = _rel_err(_train_logits(cfg, prog, tok), want)
    if fault is None:
        assert err < REL_TOL, err
    else:
        assert err > 100 * REL_TOL, (fault.__name__, err)


def test_prefill_then_decode_matches_the_reference():
    """Prefill 24 tokens, then decode 4 through the Mamba-2 conv and SSM
    states and the KV cache: each step's logits are the reference's at
    that position."""
    cfg = _cfg()
    params = _params(cfg, seed=3)
    tok = _tokens(cfg, seed=3)
    want = np.asarray(ref.logits(params, tok, cfg))
    model = build_model(cfg)
    caches, lg = model.prefill(params, {"tokens": tok[:, :24]}, cache_len=32)
    got = [lg[:, 0]]
    for t in range(24, 27):
        caches, lg = model.decode_step(params, caches, tok[:, t:t + 1],
                                       jnp.int32(t))
        got.append(lg[:, 0])
    assert _rel_err(jnp.stack(got, 1), want[:, 23:27]) < REL_TOL


def test_config_holds_the_catalog_sizes():
    """The zoo config and the benchmark's configuration file state the same
    published sizes (the file copies the catalog's config.json)."""
    cfg = get_config(ARCH)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "nemotron3-nano-p1-v5e8.json")) as f:
        pub = json.load(f)
    m = cfg.moe
    assert (cfg.d_model, cfg.vocab, cfg.n_heads, cfg.n_kv, cfg.hd) == (
        pub["hidden_size"], pub["vocab_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"])
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_chunk) == (pub["mamba_num_heads"], pub["mamba_head_dim"],
                               pub["ssm_state_size"], pub["n_groups"],
                               pub["chunk_size"])
    assert (m.num_experts, m.top_k, m.d_expert, m.num_shared, m.d_shared,
            m.routed_scale) == (
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["moe_intermediate_size"], pub["n_shared_experts"],
        pub["moe_shared_expert_intermediate_size"],
        pub["routed_scaling_factor"])
    assert m.activation == pub["mlp_hidden_act"] == "relu2"
    assert cfg.norm_eps == pub["norm_eps"]
    assert cfg.tie_embeddings == pub["tie_word_embeddings"]
    kinds = "".join({"mamba": "M", "none": "E", "attn": "*"}[d.mixer]
                    for d in cfg.pattern)
    assert kinds == ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                     "EMEMEMEME")
    assert cfg.n_layers == 52
    assert kinds[:pub["num_hidden_layers"]] == pub["hybrid_override_pattern"]
    kw = pub["graph"]["kwargs"]
    assert kw["arch"] == ARCH and kw["layers"] == pub["num_hidden_layers"]
    # 31.6B parameters in all (the catalog's "31.6B-A3.2B")
    assert 31.0e9 < cfg.param_count() < 32.2e9


def test_first_layers_keep_whole_periods():
    cfg = get_config(ARCH)
    one = cfg.first_layers(7)
    assert one.n_layers == 7 and one.period == 7
    for bad in (2, 5, 8):          # "ME" lacks attention; 5, 8 cut a period
        with pytest.raises(ValueError):
            cfg.first_layers(bad)
    jamba = get_config("jamba-1.5-large-398b")
    assert jamba.first_layers(16).n_groups == 2
    with pytest.raises(ValueError):
        jamba.first_layers(12)


# ------------------------------------------------- published widths, shapes
B, S = 8, 8192                       # the benchmark cell's traced step


def _matmul_flops(g):
    return float(g.flops[g.op_type == op_id("matmul")].sum())


def _analytic():
    """Forward matrix-product FLOPs of each layer kind at batch 8 x seq 8192,
    from the config, and each kind's gradient: every product counted once
    forward and once more per operand that carries a gradient."""
    cfg = get_config(ARCH)
    m = cfg.moe
    T, d = B * S, cfg.d_model
    H, P, N, G, c = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                     cfg.ssm_groups, cfg.ssm_chunk)
    di = H * P
    # in-projection to (z, x, B, C, dt), out-projection, and the chunked
    # SSD's products: C.B within a chunk, its scores times x, C times the
    # carried state, the state update and its normaliser
    mamba = 2 * T * d * (2 * di + 2 * G * N + H) + 2 * T * di * d + \
        2 * T * H * (c * N + c * P + 2 * N * P + N)
    # q, k, v, o projections; scores and values over every 1024 x 1024
    # block (the chunked attention computes the masked blocks too)
    attn = 2 * T * d * (cfg.n_heads + 2 * cfg.n_kv) * cfg.hd + \
        2 * T * cfg.n_heads * cfg.hd * d + 4 * B * cfg.n_heads * S * S * cfg.hd
    E, k, C = m.num_experts, m.top_k, int(S * m.top_k * m.capacity_factor /
                                          m.num_experts)
    router, slots = 2 * T * d * E, 2 * T * E * C * k   # one-hots over k
    move = 2 * T * d * E * C                           # dispatch, combine
    experts = 2 * 2 * E * C * B * d * m.d_expert       # relu^2: up, down
    shared = 2 * 2 * T * d * m.d_shared
    moe = router + 2 * slots + 2 * move + experts + shared
    # the dispatch one-hot has no gradient, the combine one-hot one operand
    # with it, the dispatched tokens one (their one-hot carries none)
    moe_grad = 3 * router + slots + 2 * slots + 2 * move + 3 * move + \
        3 * experts + 3 * shared
    head = 2 * T * d * cfg.vocab
    return {"M": (mamba, 3 * mamba), "E": (moe, moe_grad),
            "*": (attn, 3 * attn), "head": (head, 4 * head)}


@pytest.mark.parametrize("kind", ["M", "E", "*"])
def test_layer_matmul_flops_at_published_widths(kind):
    """One layer of each kind, traced from shapes at the published widths:
    its forward and gradient graphs' matrix-product FLOPs are the counts
    written from the config, to 0.1% (measured: under 1.1e-4, the router's
    and norms' small products left out of the count)."""
    from repro.graphs.jaxpr_extract import extract
    from repro.models import blocks
    cfg = get_config(ARCH)
    desc = cfg.pattern["MEMEM*E".index(kind)]
    lp = jax.eval_shape(lambda key: blocks.init_layer(key, cfg, desc,
                                                      jnp.bfloat16),
                        jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
    pos = jnp.zeros((B, S), jnp.int32)

    def f(lp, x):
        y, _, aux = blocks.apply_layer(lp, x, cfg, desc, positions=pos,
                                       mode="train")
        return jnp.sum(y.astype(jnp.float32)) + aux

    fwd, grad = _analytic()[kind]
    got_f = _matmul_flops(extract(f, lp, x, expand=64))
    got_g = _matmul_flops(extract(
        lambda lp, x: jax.grad(f, argnums=(0, 1))(lp, x), lp, x, expand=64))
    assert got_f == pytest.approx(fwd, rel=1e-3)
    assert got_g == pytest.approx(grad, rel=1e-3)


def test_one_period_step_graph_at_published_widths(tmp_path):
    """The benchmark cell's graph: the one-period training step at seq 8192,
    expand 64.  Its node count lies in the configuration's 40k-60k band, and
    its matrix products are the layers' gradients (3 Mamba-2, 3 MoE, 1
    attention), the LM head's checkpointed cross-entropy, and at most one
    more forward pass of the layers for the group's remat (the backward
    recomputes only what it reads)."""
    from repro.graphs.jaxpr_extract import extract_arch
    g = extract_arch(ARCH, mode="grad", seq=S, batch=B, layers=7, expand=64,
                     cache_dir=str(tmp_path))
    assert 40_000 <= g.num_nodes <= 60_000, g.num_nodes
    assert int(g.op_type.max()) < 25          # featurize embeds 25 op ids
    a = _analytic()
    grads = 3 * a["M"][1] + 3 * a["E"][1] + a["*"][1] + a["head"][1]
    recompute = 3 * a["M"][0] + 3 * a["E"][0] + a["*"][0]
    got = _matmul_flops(g)
    assert grads * (1 - 1e-3) <= got <= (grads + recompute) * (1 + 1e-3)


def test_model_step_places_through_the_api(tmp_path, monkeypatch):
    """The normal path: the zoo's training step as a named graph family,
    placed by ``api.place`` with the fine-tuning trainer."""
    from repro.api import Budget, place
    from repro.core.policy import PolicyConfig
    from repro.graphs.jaxpr_extract import CACHE_ENV
    from repro.graphs.synthetic import make_graph
    from repro.sim import tpu_v5e_topology
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    g = make_graph("model_step", arch=ARCH, reduced=True, seq=16, batch=2)
    assert g.name == f"{ARCH}-r-grad-s16"
    topo = tpu_v5e_topology(4).with_mem_caps(g.total_mem() / 4 * 2.5)
    pcfg = PolicyConfig(hidden=16, gnn_layers=1, op_emb=8, placer_layers=1,
                        heads=2, ffn=32, window=16, max_devices=4)
    plan = place(g, topo, pcfg=pcfg, method="finetune",
                 budget=Budget(finetune_iters=2, samples=2))
    assert plan.method == "finetune" and plan.valid and plan.makespan > 0
    assert plan.placement.shape == (g.num_nodes,)


def test_benchmark_cell_runs_correct_at_reduced_size(monkeypatch, tmp_path):
    """The benchmark's cell ``nemotron3-nano-finetune``, with its graph cut
    to the reduced config and the policy to test widths, run traced on the
    CPU: set-up extracts the step graph through ``model_step``, the checked
    PPO steps match the plain reference, and the traced window reports
    every per-layer metric that lists the cell."""
    import importlib.util
    import sys
    from repro.graphs.jaxpr_extract import CACHE_ENV
    sys.path[:0] = [ROOT]
    try:
        from chipbench import harness
        spec = importlib.util.spec_from_file_location(
            "chipbench_run_nemotron", os.path.join(ROOT, "chipbench",
                                                   "run.py"))
        run_mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(run_mod)
    finally:
        sys.path.remove(ROOT)
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    name = "nemotron3-nano-finetune"
    cfg = harness.config_file(harness.benchmark(), "nemotron3-nano-p1-v5e8")
    cfg["graph"]["kwargs"].update(seq=16, batch=2, expand=None, reduced=True)
    cfg["policy"].update(hidden=32, ffn=64, window=16, segment=128,
                         gnn_chunk=64)
    result, checks = run_mod.run_cell(
        name, 2 ** 33 + 7, 1.0, True, require_chip=False, config=cfg,
        peaks={"devices": {"cpu": {"bf16_flops": 1e12}}})
    assert result["correct"], [(c.name, c.value) for c in checks]
    want = {m["name"] for m in harness.benchmark()["per_layer"]
            if name in m.get("workloads", [name])}
    assert set(result["metrics"]) == want
