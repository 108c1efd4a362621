"""The FLOP counts behind ``mfu.*``: exact against the matrix products in
the program's own jaxprs, and close to XLA's ``cost_analysis()`` flops for
the same compiled programs, at a small size on the CPU."""
import math
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import flops, weights  # noqa: E402

POLICY = {"hidden": 32, "gnn_layers": 2, "op_emb": 8, "placer_layers": 2,
          "heads": 4, "ffn": 64, "window": 16, "max_devices": 8,
          "use_superposition": True, "num_op_types": 25,
          "num_numeric_features": 10, "num_device_features": 6}


def _dot_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            out = eqn.outvars[0].aval.shape
            total += 2.0 * math.prod(out) * math.prod(lhs[i] for i in lc)
        for p in eqn.params.values():
            subs = p if isinstance(p, (list, tuple)) else [p]
            for sub in subs:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    total += _dot_flops(getattr(inner, "jaxpr", inner))
    return total


def _graph_batch(n):
    from repro.core.featurize import featurize
    from repro.graphs import synthetic
    from repro.sim import p100_topology
    g = synthetic.rnnlm(2, time_steps=(n - 12) // 25)
    return featurize(g, topo=p100_topology(4)), g.num_nodes


def _programs(n):
    from repro.core import gnn, placer, superposition
    gb, _ = _graph_batch(n)
    params = weights.make(0, POLICY)

    def encode(p, gb):
        h = gnn.apply(p["gnn"], gb)
        return superposition.gain(p["sp"], gnn.graph_summary(h, gb.node_mask))

    def tf(p, gb, pl):
        h = gnn.apply(p["gnn"], gb)
        return placer.apply_tf(p["placer"], h, gb.node_mask, pl, None,
                               gb.mem_frac, gb.comp_frac, gb.dev_feats,
                               window=POLICY["window"], heads=POLICY["heads"],
                               num_devices=4)
    pl = jnp.zeros((gb.op.shape[0],), jnp.int32)
    return (encode, (params, gb)), (tf, (params, gb, pl)), gb.op.shape[0]


@pytest.mark.parametrize("n", [137, 400])
def test_counts_equal_the_matrix_products_in_the_jaxprs(n):
    (enc, enc_args), (tf, tf_args), pad_n = _programs(n)
    got = _dot_flops(jax.make_jaxpr(enc)(*enc_args).jaxpr)
    assert got == flops.encoder(pad_n, POLICY)
    got = _dot_flops(jax.make_jaxpr(tf)(*tf_args).jaxpr)
    # the program computes the whole window at every position, the masked
    # part too; that is what count_masked counts
    want = (flops.encoder(pad_n, POLICY) - flops.encoder(0, POLICY)
            + flops.placer(pad_n, POLICY, count_masked=True))
    assert got == want


def test_counts_are_close_to_xla_cost_analysis():
    """XLA also counts elementwise work (layer norms, softmax, sigmoid,
    the neighbor max, masking) and the masked part of the attention
    window, none of which the model's FLOPs hold.  At hidden 32 and 324
    padded nodes that adds 20% to the encoder and 13% to the
    teacher-forced pass: the count may never exceed XLA's and may fall
    short of it by no more than 30%."""
    (enc, enc_args), (tf, tf_args), pad_n = _programs(400)
    for fn, args, ours in (
            (enc, enc_args, flops.encoder(pad_n, POLICY)),
            (tf, tf_args, flops.encoder(pad_n, POLICY)
             - flops.encoder(0, POLICY) + flops.placer(pad_n, POLICY))):
        cost = jax.jit(fn).lower(*args).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        xla = float(cost["flops"])
        assert ours <= xla <= 1.3 * ours, (ours, xla)


def test_padding_is_not_counted():
    assert flops.attended(10, 4) == 1 + 2 + 3 + 4 * 7
    assert flops.attended(10, 4, count_masked=True) == 40
    assert flops.placer(100, POLICY) < flops.placer(100, POLICY,
                                                   count_masked=True)
    assert flops.ppo_iteration(100, POLICY, 4, 1) == (
        flops.sample(100, POLICY, 4)
        + 4 * (flops.encoder(100, POLICY) + 4 * flops.placer(100, POLICY)))
