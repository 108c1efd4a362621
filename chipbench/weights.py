"""GDP policy weights made from the seed, on the device, in one jitted call.

The benchmark makes the weights itself so that the program and the plain
reference start from the same numbers and neither takes them from the
other.  The tree has the layout the GDP policy reads: a GraphSAGE encoder
(``gnn``), the superposition gain (``sp``) and the placer (``placer``).
Scales follow the usual fan-in rule; the residual outputs, the device
head and the gain's last layer start small so that a fresh policy is
near-uniform over devices and the gain is near 1.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp


def _dense(key, d_in: int, d_out: int, scale=None) -> Dict[str, Any]:
    scale = 1.0 / math.sqrt(d_in) if scale is None else scale
    return {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale,
            "b": jnp.zeros((d_out,), jnp.float32)}


def _norm(d: int) -> Dict[str, Any]:
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


@partial(jax.jit, static_argnames=("shape",))
def _make(key, shape):
    (hidden, gnn_layers, op_emb, placer_layers, ffn, max_devices,
     num_op_types, num_feats, num_dev_feats) = shape
    ks = iter(jax.random.split(key, 4 + 2 * gnn_layers + 2 + 6 * placer_layers
                               + 4))
    gnn = {"op_emb": jax.random.normal(next(ks), (num_op_types + 1, op_emb))
           * 0.02,
           "in": _dense(next(ks), op_emb + num_feats, hidden),
           "layers": [{"agg": _dense(next(ks), hidden, hidden),
                       "upd": _dense(next(ks), 2 * hidden, hidden)}
                      for _ in range(gnn_layers)]}
    sp = {"fc1": _dense(next(ks), 2 * hidden, hidden),
          "fc2": _dense(next(ks), hidden, hidden, 1e-3)}
    layers = [{"ln1": _norm(hidden),
               "wq": _dense(next(ks), hidden, hidden),
               "wk": _dense(next(ks), hidden, hidden),
               "wv": _dense(next(ks), hidden, hidden),
               "wo": _dense(next(ks), hidden, hidden, 1e-2),
               "ln2": _norm(hidden),
               "w1": _dense(next(ks), hidden, ffn),
               "w2": _dense(next(ks), ffn, hidden, 1e-2)}
              for _ in range(placer_layers)]
    placer = {"layers": layers,
              "dev_emb": jax.random.normal(next(ks),
                                           (max_devices + 1, hidden)) * 0.02,
              "ctx": _dense(next(ks), 2 * max_devices + 2, hidden, 0.1),
              "ln_f": _norm(hidden),
              "head": _dense(next(ks), hidden, max_devices, 1e-2),
              "dev_key": _dense(next(ks), num_dev_feats, hidden, 0.1)}
    return {"gnn": gnn, "sp": sp, "placer": placer}


def make(seed: int, policy: Dict[str, Any]) -> Dict[str, Any]:
    """The weight tree for the configuration's ``policy`` block."""
    shape = tuple(int(policy[k]) for k in (
        "hidden", "gnn_layers", "op_emb", "placer_layers", "ffn",
        "max_devices", "num_op_types", "num_numeric_features",
        "num_device_features"))
    return _make(jax.random.PRNGKey(seed), shape)
