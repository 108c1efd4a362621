"""Plain GDP policy and PPO step in jax.numpy: the reference for the
program's GNN, placer, teacher-forced log-probs and update.

Written from the method (arXiv:1910.01578 §3) as the program states it:

* GraphSAGE encoder: op embedding and numeric features into a dense
  layer, then per layer ``z = sigmoid(W h)``, the max of ``z`` over the
  node's kept neighbors (0 for a node without any), and
  ``h = relu(U [h, max])``.
* Superposition gain ``c = 1 + tanh(F2 relu(F1 [mean h, max h]))``
  multiplies the input of every dense layer of the placer.
* Placer: per node the input is ``h + E[previous device] + C ctx``, with
  ``ctx`` the memory and compute already placed on each device and the
  node's own two fractions; pre-norm layers of causal attention over the
  last ``window`` nodes and a ReLU feed-forward; the head adds a bilinear
  device-capability term; devices past the fleet are masked and, with
  ``mask_full_devices``, devices the node would push past their cap are
  masked unless every device would be.
* Segments: with a segment length set, keys and values from an earlier
  segment enter a later one's attention with their gradient stopped
  (Transformer-XL recurrence); the values are unchanged.
* PPO: the clipped surrogate on per-node ratios with one advantage per
  sample, less ``entropy_coef`` times the mean entropy; gradients with
  non-finite entries zeroed, clipped to a global norm, then Adam.

The attention is computed one offset at a time, so nothing of size
``N x window x hidden`` is held; each layer is rematerialised in the
backward pass.  ``precision`` names what the inputs of every matrix
product hold: ``float32`` (the reference; callers run it at the highest
matmul precision), or ``float8`` (the control: each operand scaled per
tensor into float8 e4m3 and rounded, the step below the bfloat16 inputs
that a TPU's default precision gives the program's float32 products).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e9


def _scaled_round(x, fmt, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(fmt).astype(jnp.float32) * s


@jax.custom_vjp
def _float8(x):
    """x at float8 resolution, scaled per tensor into the format's range:
    e4m3 forward, and the gradient that flows back rounded to e5m2 with
    its own scale, as float8 training does."""
    return _scaled_round(x, jnp.float8_e4m3fn, 448.0)


_float8.defvjp(lambda x: (_float8(x), None),
               lambda _, g: (_scaled_round(g, jnp.float8_e5m2, 57344.0),))


ROUND = {"float32": lambda x: x, "float8": _float8}


def _dense(p, x, rnd):
    return rnd(x) @ rnd(p["w"]) + p["b"]


def _ln(p, x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def encode(gp, inp, rnd):
    """Node embeddings [N, H] of the GraphSAGE encoder."""
    x = jnp.concatenate([gp["op_emb"][inp["op"]], inp["feats"]], -1)
    h = jax.nn.relu(_dense(gp["in"], x, rnd))
    mask = inp["nbr_mask"]
    idx = jnp.where(mask, inp["nbr_idx"], 0)
    for lp in gp["layers"]:
        z = jax.nn.sigmoid(_dense(lp["agg"], h, rnd))
        g = jnp.where(mask[..., None], z[idx], NEG)
        agg = jnp.where(mask.any(-1)[:, None], g.max(1), 0.0)
        h = jax.nn.relu(_dense(lp["upd"], jnp.concatenate([h, agg], -1),
                               rnd))
    return h


def gain(sp, h, rnd, node_mask=None):
    """Superposition gain from the mean and max of the real nodes' h."""
    if node_mask is None:
        x0 = jnp.concatenate([h.mean(0), h.max(0)])
    else:
        m = node_mask[:, None]
        x0 = jnp.concatenate([(h * m).sum(0) / m.sum(),
                              jnp.where(m > 0, h, NEG).max(0)])
    return 1.0 + jnp.tanh(_dense(sp["fc2"], jax.nn.relu(
        _dense(sp["fc1"], x0, rnd)), rnd))


def _shift(a, o):
    """a[i - o] at row i, zeros for i < o."""
    if o == 0:
        return a
    return jnp.concatenate([jnp.zeros((o,) + a.shape[1:], a.dtype), a[:-o]])


def _attention(q, k, v, window: int, segment: Optional[int], rnd):
    q, k, v = rnd(q), rnd(k), rnd(v)
    n, heads, hd = q.shape
    w = min(window, n)
    i = jnp.arange(n)
    scale = 1.0 / np.sqrt(hd)
    ks, vs = jax.lax.stop_gradient(k), jax.lax.stop_gradient(v)
    scores, values = [], []
    for o in range(w):
        kk, vv = _shift(k, o), _shift(v, o)
        if segment is not None:
            same = (i // segment == (i - o) // segment)[:, None, None]
            kk = jnp.where(same, kk, _shift(ks, o))
            vv = jnp.where(same, vv, _shift(vs, o))
        s = (q * kk).sum(-1) * scale
        scores.append(jnp.where((i >= o)[:, None], s, NEG))
        values.append(vv)
    a = rnd(jax.nn.softmax(jnp.stack(scores, -1), -1))
    return sum(a[..., o, None] * values[o] for o in range(w))


def logits(pp, h, c, inp, placements, *, num_devices: int, window: int,
           heads: int, segment: Optional[int], mask_full: bool, rnd):
    """Teacher-forced device logits [N, Dmax] of one placement i[N]."""
    dmax = pp["head"]["b"].shape[0]
    n, hid = h.shape
    prev = jnp.concatenate([jnp.array([dmax]), placements[:-1]])
    onehot = jax.nn.one_hot(placements, dmax, dtype=jnp.float32)
    mem_cum = jnp.cumsum(onehot * inp["mem_frac"][:, None], 0)
    comp_cum = jnp.cumsum(onehot * inp["comp_frac"][:, None], 0)
    zero = jnp.zeros((1, dmax))
    mem_before = jnp.concatenate([zero, mem_cum[:-1]])
    comp_before = jnp.concatenate([zero, comp_cum[:-1]])
    ctx = jnp.concatenate([mem_before, comp_before, inp["mem_frac"][:, None],
                           inp["comp_frac"][:, None]], -1)
    x = h + pp["dev_emb"][prev] + _dense(pp["ctx"], ctx, rnd)
    hd = hid // heads

    def layer(lp, x):
        xn = _ln(lp["ln1"], x) * c
        q, k, v = (_dense(lp[w], xn, rnd).reshape(n, heads, hd)
                   for w in ("wq", "wk", "wv"))
        out = _attention(q, k, v, window, segment, rnd).reshape(n, hid)
        x = x + _dense(lp["wo"], out * c, rnd)
        f = jax.nn.relu(_dense(lp["w1"], _ln(lp["ln2"], x) * c, rnd))
        return x + _dense(lp["w2"], f, rnd)

    for lp in pp["layers"]:
        x = jax.checkpoint(layer)(lp, x)
    out = _ln(pp["ln_f"], x) * c
    df = jnp.zeros((dmax, inp["dev_feats"].shape[1]))
    df = df.at[:inp["dev_feats"].shape[0]].set(inp["dev_feats"])
    keys = _dense(pp["dev_key"], df, rnd)
    # the device term as each key less the first device's, plus the first
    # device's: the same logits, but the part common to all of a node's
    # logits is summed per node before the sums over nodes, so where the
    # softmax cancels it (identical devices) its gradient is nought to
    # rounding and not the remainder of large sums over 50k nodes
    ro = rnd(out)
    lg = _dense(pp["head"], out, rnd) + (
        ro @ rnd(keys - keys[:1]).T + (ro @ rnd(keys[0]))[:, None]
    ) / np.sqrt(hid)
    lg = jnp.where(jnp.arange(dmax) < num_devices, lg, NEG)
    if mask_full:
        cap = jnp.zeros(dmax).at[:inp["dev_mem_cap"].shape[0]].set(
            inp["dev_mem_cap"])
        ok = (mem_before + inp["mem_frac"][:, None]) <= cap * (1 - 1e-6)
        ok = ok & (jnp.arange(dmax) < num_devices)
        lg = jnp.where(ok | ~ok.any(-1, keepdims=True), lg, NEG)
    return lg


def logp_entropy(params, inp, placements, *, policy: Dict[str, Any],
                 num_devices: int, precision: str = "float32",
                 temperature: float = 1.0):
    """(per-node log-prob [M, N] of placements i[M, N], mean entropy).

    ``inp`` may carry a ``node_mask``: nodes past the real ones (padding
    with no edges, appended after every real node) then stay out of the
    gain; causal attention keeps them out of every real node's logits.
    The entropy is then only meaningful without padding."""
    rnd = ROUND[precision]
    h = encode(params["gnn"], inp, rnd)
    c = (gain(params["sp"], h, rnd, inp.get("node_mask"))
         if policy["use_superposition"] else 1.0)

    def one(pl):
        lg = logits(params["placer"], h, c, inp, pl, num_devices=num_devices,
                    window=policy["window"], heads=policy["heads"],
                    segment=policy.get("segment"),
                    mask_full=policy["mask_full_devices"], rnd=rnd)
        lp = jax.nn.log_softmax(lg / temperature, -1)
        ent = -(jnp.exp(lp) * lp).sum(-1)
        return jnp.take_along_axis(lp, pl[:, None], -1)[:, 0], ent.mean()

    node_lp, ent = jax.vmap(one)(placements)
    return node_lp, ent.mean()


def ppo_loss(params, inp, placements, adv, *, policy, ppo, num_devices,
             entropy_coef, precision):
    lp, ent = logp_entropy(params, inp, placements, policy=policy,
                           num_devices=num_devices, precision=precision)
    ratio = jnp.exp(jnp.clip(lp - jax.lax.stop_gradient(lp), -10.0, 10.0))
    a = adv[:, None]
    eps = ppo["clip_eps"]
    surr = jnp.minimum(ratio * a, jnp.clip(ratio, 1 - eps, 1 + eps) * a)
    pg = -surr.sum(-1) / placements.shape[1]
    return pg.mean() - entropy_coef * ent


def advantages(rewards: np.ndarray, baseline: Dict[str, float],
               adv_norm: bool) -> np.ndarray:
    """One advantage per sample: the float32 reward less the running
    average of every earlier reward on the graph (this batch's mean on
    the first step), normalized over the batch; updates ``baseline``."""
    count, value = baseline["count"], baseline["value"]
    adv = rewards - (value if count else float(rewards.mean()))
    if adv_norm and adv.std() > 1e-6:
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    baseline["value"] = (value * count + float(rewards.sum())) / (
        count + rewards.size)
    baseline["count"] = count + rewards.size
    return adv.astype(np.float32)


def ppo_step(params, adam, inp, placements, adv, entropy_coef, *, policy,
             ppo, num_devices, precision):
    """One PPO update; returns (loss, clipped gradient, params, adam)."""
    def loss_fn(p):
        return ppo_loss(p, inp, placements, adv, policy=policy, ppo=ppo,
                        num_devices=num_devices, entropy_coef=entropy_coef,
                        precision=precision)

    loss, g = jax.value_and_grad(loss_fn)(params)
    g = jax.tree_util.tree_map(
        lambda a: jnp.where(jnp.isfinite(a), a, 0.0), g)
    norm = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree_util.tree_leaves(g)))
    g = jax.tree_util.tree_map(
        lambda a: a * jnp.minimum(1.0, ppo["grad_clip"] / jnp.maximum(
            norm, 1e-9)), g)
    t = adam["t"] + 1
    b1, b2, lr, eps = 0.9, 0.999, ppo["lr"], 1e-8
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                               adam["m"], g)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               adam["v"], g)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (
            jnp.sqrt(v / (1 - b2 ** t)) + eps), params, m, v)
    return loss, g, params, {"t": t, "m": m, "v": v}


def adam_zeros(params):
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"t": jnp.zeros((), jnp.int32), "m": z, "v": z}
