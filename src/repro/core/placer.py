"""Autoregressive Transformer placement network (paper §3.2).

Seq2seq decoder over nodes in topological order: node *i*'s device
distribution conditions on the graph embedding of every node (via the GNN)
and, critically, on the devices already assigned to nodes *< i* — the
feedback that lets the policy express "co-locate me with my neighbors" and
break the device-permutation symmetry of the reward.

Design notes mapped to the paper:

* **No positional embedding** — topology lives in the GNN output; the paper
  removes positions "to prevent overfitting node identifications".
* **Bounded attention context**: the paper uses Transformer-XL segment
  recurrence (cached previous segment, gradients stopped).  We implement
  the equivalent bounded-cost long-context mechanism as *causal
  sliding-window attention* of width ``window``: training is a single
  teacher-forced parallel pass (reusing the chunked online-softmax
  attention from the model zoo), sampling is an exact step-by-step scan
  with ring-buffer KV caches.  Within-window gradients flow (a strict
  improvement over stop-gradient memory); the O(N·W) cost and >50k-node
  scalability story are identical.  Recorded in DESIGN.md §8.
* **Superposition** gain ``c`` (Eq. 4) modulates every dense layer input;
  ``None`` disables it (Fig. 3 ablation).
* ``use_attention=False`` removes the attention sublayer (Fig. 3 ablation).
* **Device-aware head** (heterogeneous-topology extension): each device's
  logit gains a bilinear term ``out·W·devfeat_d`` over the normalized
  per-device capability table (``featurize.device_features``), so the
  decoder can rank devices by speed/memory/connectivity per node.  On a
  uniform pool all rows are equal, the term shifts every valid device's
  logit identically, and the distribution reduces to the homogeneous one.
* **Incumbent-conditioned decode** (migration-aware re-placement): an
  optional additive per-node logit bias ``incumbent_bias`` [N, Dmax]
  tilts each node toward the device its state already lives on, weighted
  by the node's memory footprint — the decoder trades makespan against
  data movement when re-placing after a fleet change.  ``None`` (the
  default) is bit-identical to the unbiased decode: the bias is threaded
  as a pytree leaf-or-None through every path, so the off-path traces
  the exact same program as before.  Applied in the fixed order
  ``_head_logits → + bias → _mask_full_devices → / temperature`` in BOTH
  the teacher-forced and AR paths, so PPO ratios stay exact and a full
  device can never be resurrected by the bias.

The teacher-forced pass and the sampling scan share all parameters and
masks, so logp(sampled placement) is exact for PPO.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import nn
from repro.core.featurize import NUM_DEVICE_FEATURES
from repro.kernels import ops as kops
from repro.core.superposition import modulate
from repro.obs import jaxprof
from repro.obs.trace import get_tracer

NEG = -1e9


def init(key, hidden: int, num_layers: int = 2, heads: int = 4,
         ffn: int = 512, max_devices: int = 16) -> Dict[str, Any]:
    ks = nn.split_keys(key, 6 * num_layers + 4)
    layers: List[Dict[str, Any]] = []
    for l in range(num_layers):
        k = ks[6 * l: 6 * l + 6]
        layers.append({
            "ln1": nn.layernorm_init(hidden),
            "wq": nn.dense_init(k[0], hidden, hidden),
            "wk": nn.dense_init(k[1], hidden, hidden),
            "wv": nn.dense_init(k[2], hidden, hidden),
            "wo": nn.dense_init(k[3], hidden, hidden, scale=1e-2),
            "ln2": nn.layernorm_init(hidden),
            "w1": nn.dense_init(k[4], hidden, ffn),
            "w2": nn.dense_init(k[5], ffn, hidden, scale=1e-2),
        })
    return {
        "layers": layers,
        "dev_emb": nn.embedding_init(ks[-3], max_devices + 1, hidden),
        # resource-aware decoder context: running per-device memory and
        # compute load (2*Dmax) + this node's own mem/comp fractions (2)
        "ctx": nn.dense_init(ks[-1], 2 * max_devices + 2, hidden, scale=0.1),
        "ln_f": nn.layernorm_init(hidden),
        "head": nn.dense_init(ks[-2], hidden, max_devices, scale=1e-2),
        # device-capability keys for the bilinear head term
        "dev_key": nn.dense_init(ks[-4], NUM_DEVICE_FEATURES, hidden,
                                 scale=0.1),
    }


# --------------------------------------------------------------- internals
def _ffn(lp, x, c):
    h = jax.nn.relu(nn.dense(lp["w1"], modulate(c, nn.layernorm(lp["ln2"], x))))
    return x + nn.dense(lp["w2"], h)


def _proj_qkv(lp, x, c, heads):
    h = x.shape[-1]
    hd = h // heads
    xn = nn.layernorm(lp["ln1"], x)
    q = nn.dense(lp["wq"], modulate(c, xn)).reshape(*x.shape[:-1], heads, hd)
    k = nn.dense(lp["wk"], modulate(c, xn)).reshape(*x.shape[:-1], heads, hd)
    v = nn.dense(lp["wv"], modulate(c, xn)).reshape(*x.shape[:-1], heads, hd)
    return q, k, v


def _inputs(params, h, prev_dev, ctx):
    """Decoder input: GNN embedding + prev-device embedding + resource ctx.

    ctx: [..., 2*Dmax+2] — per-device running mem/comp load plus this
    node's own mem/comp fraction.  Exactly reproducible teacher-forced
    (cumsum by device) and in the AR scan (carried accumulators).
    """
    return h + params["dev_emb"][prev_dev] + nn.dense(params["ctx"], ctx)


def _dev_keys(params, dev_feats: Optional[jnp.ndarray]) -> jnp.ndarray:
    """[Dmax, H] capability keys; zero-feature rows (padding, or a
    featurize() without topo) all map to the bias row — a constant logit
    shift that cancels in the softmax."""
    dmax = params["head"]["b"].shape[0]
    df = jnp.zeros((dmax, NUM_DEVICE_FEATURES))
    if dev_feats is not None and dev_feats.shape[0]:
        df = df.at[:dev_feats.shape[0]].set(dev_feats[:dmax])
    return nn.dense(params["dev_key"], df)


def _head_logits(params, x, c, num_devices, dev_keys):
    out = nn.layernorm(params["ln_f"], x)
    outm = modulate(c, out)
    logits = nn.dense(params["head"], outm)
    logits = logits + outm @ dev_keys.T / jnp.sqrt(jnp.float32(out.shape[-1]))
    dmax = logits.shape[-1]
    return jnp.where((jnp.arange(dmax) < num_devices), logits, NEG)


def _cap_vector(params, dev_mem_cap: Optional[jnp.ndarray]
                ) -> Optional[jnp.ndarray]:
    """[Dmax] per-device memory caps in mem_frac units (0 for padding),
    or None when the featurizer had no topology (masking disabled)."""
    if dev_mem_cap is None or not dev_mem_cap.shape[0]:
        return None
    dmax = params["head"]["b"].shape[0]
    cap = jnp.zeros((dmax,))
    return cap.at[:dev_mem_cap.shape[0]].set(dev_mem_cap[:dmax])


def _mask_full_devices(logits: jnp.ndarray, mem_used: jnp.ndarray,
                       mem_frac, cap: jnp.ndarray,
                       num_devices: int) -> jnp.ndarray:
    """Memory-aware decode mask: devices that the node would push past
    their cap get NEG logits, so sampled placements are feasible by
    construction whenever greedy feasibility exists.  If EVERY device
    would overflow (a graph that cannot fit at all), the mask is a no-op
    — the simulator's validity check remains the arbiter.

    The tolerance is CONSERVATIVE (devices are closed slightly *before*
    the cap): the mask accumulates f32 ``mem_frac`` while the simulator
    sums raw bytes, so an exact-boundary admission could round past the
    strict byte-level check and be judged invalid — closing early keeps
    the feasibility guarantee at the cost of a sliver of capacity.

    ``mem_used``/``mem_frac`` broadcast: [..., Dmax] running loads and
    [...] node fractions (works for the AR step and the TF batch alike).
    """
    dmax = logits.shape[-1]
    ok = (mem_used + jnp.expand_dims(mem_frac, -1)) <= cap * (1 - 1e-6)
    ok = ok & (jnp.arange(dmax) < num_devices)
    any_ok = jnp.any(ok, axis=-1, keepdims=True)
    return jnp.where(ok | ~any_ok, logits, NEG)


# ------------------------------------------------------------ teacher-forced
def _band_attention(q, kbuf, vbuf, base, window: int) -> jnp.ndarray:
    """Causal W-band attention as blocked windows of dense products.

    q: [S, heads, hd]; kbuf/vbuf: [W-1+S, heads, hd] (the W-1 columns
    before q[0], then q's own); ``base``: global index of q[0], traced or
    not.  Query ``i`` attends buffer columns ``[i, i + W - 1]`` whose
    global index ``base + col - (W-1)`` is >= 0 — the AR ring buffer's
    mask (j <= i, i - j < W, inclusive self).

    Queries go in blocks of W rows; block b's band lies in buffer blocks
    b and b+1, so its keys are one static concat and its scores one dense
    [heads, W, 2W] tile, masked to the band.  Twice the band's products,
    but no gather, scatter or dynamic index, in the pass or its gradient;
    a W that does not divide S, or exceeds it, only adds padding.
    """
    s, heads, hd = q.shape
    w = window
    nb = -(-s // w)
    qb = jnp.pad(q, ((0, nb * w - s), (0, 0), (0, 0))).reshape(
        nb, w, heads, hd)

    def pairs(buf):                              # [nb, 2W, heads, hd]
        blk = jnp.pad(buf, ((0, (nb + 1) * w - buf.shape[0]), (0, 0),
                            (0, 0))).reshape(nb + 1, w, heads, hd)
        return jnp.concatenate([blk[:-1], blk[1:]], axis=1)

    kb, vb = pairs(kbuf), pairs(vbuf)
    sc = jnp.einsum("bihd,bjhd->bhij", qb, kb) / jnp.sqrt(jnp.float32(hd))
    rel = jnp.arange(2 * w)[None, :] - jnp.arange(w)[:, None]   # [W, 2W]
    col = base + jnp.arange(nb)[:, None] * w + jnp.arange(2 * w) - (w - 1)
    valid = ((rel >= 0) & (rel < w))[None] & (col >= 0)[:, None, :]
    sc = jnp.where(valid[:, None], sc, NEG)
    aw = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhij,bjhd->bihd", aw, vb)
    return out.reshape(nb * w, heads, hd)[:s]


def _banded_attention(q, k, v, window: int) -> jnp.ndarray:
    """Causal sliding-window attention via band gather.

    q,k,v: [N, heads, hd].  Scores are [N, heads, W] — O(N·W), never O(N²).
    Matches the AR ring-buffer mask exactly (j<=i, i-j<W, inclusive self).
    """
    n, heads, hd = q.shape
    w = min(window, n)
    offs = jnp.arange(w) - (w - 1)                       # -(w-1)..0
    idx = jnp.arange(n)[:, None] + offs[None, :]         # [N, W]
    valid = idx >= 0
    idxc = jnp.clip(idx, 0, n - 1)
    kb, vb = k[idxc], v[idxc]                            # [N, W, heads, hd]
    sc = jnp.einsum("nhd,nwhd->nhw", q, kb) / jnp.sqrt(jnp.float32(hd))
    sc = jnp.where(valid[:, None, :], sc, NEG)
    aw = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("nhw,nwhd->nhd", aw, vb)


def _tf_ctx(params, placements: jnp.ndarray, node_mask: jnp.ndarray,
            mem_frac: jnp.ndarray, comp_frac: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(prev-device [N], resource ctx [N, 2*Dmax+2], mem_before [N, Dmax])
    for a TF pass.

    Node i sees devices of nodes < i (shifted by one; the first node sees
    the ``start`` symbol Dmax) and the per-device running loads BEFORE it
    (exclusive cumsum) — shared by the monolithic and segmented passes so
    both consume bit-identical decoder inputs.  ``mem_before`` also feeds
    the memory-aware decode mask.
    """
    dmax = params["head"]["b"].shape[0]
    prev = jnp.concatenate([jnp.array([dmax], jnp.int32),
                            placements[:-1].astype(jnp.int32)])
    onehot = jax.nn.one_hot(placements, dmax) * node_mask[:, None]
    mem_cum = jnp.cumsum(onehot * mem_frac[:, None], axis=0)
    comp_cum = jnp.cumsum(onehot * comp_frac[:, None], axis=0)
    zero = jnp.zeros((1, dmax))
    mem_before = jnp.concatenate([zero, mem_cum[:-1]], axis=0)
    comp_before = jnp.concatenate([zero, comp_cum[:-1]], axis=0)
    ctx = jnp.concatenate([mem_before, comp_before,
                           mem_frac[:, None], comp_frac[:, None]], axis=-1)
    return prev, ctx, mem_before


def apply_tf(params: Dict[str, Any], h: jnp.ndarray, node_mask: jnp.ndarray,
             placements: jnp.ndarray, c: Optional[jnp.ndarray],
             mem_frac: jnp.ndarray, comp_frac: jnp.ndarray,
             dev_feats: Optional[jnp.ndarray] = None, *,
             window: int = 256, heads: int = 4, num_devices: int = 4,
             use_attention: bool = True,
             dev_mem_cap: Optional[jnp.ndarray] = None,
             mask_full: bool = False,
             incumbent_bias: Optional[jnp.ndarray] = None,
             attn_impl: str = "jnp") -> jnp.ndarray:
    """Parallel logits for given placements (PPO ratio path).

    h: [N, H] (topo order); placements: [N] int32.  Returns device logits
    [N, Dmax].  Compiled shapes scale with N; for paper-scale graphs use
    :func:`apply_tf_segmented`, which agrees to f32 rounding.  ``mask_full``
    applies the memory-aware decode mask (must match the sampling side
    so PPO ratios stay exact).  ``incumbent_bias`` [N, Dmax] (or None)
    is added to the head logits before the mask — same order as the AR
    paths, so biased ratios stay exact too.  ``attn_impl="pallas_band"``
    computes the window band through the block-sparse pallas kernel
    instead of the gather (tolerance-pinned parity; the default stays
    the golden-pinned gather).
    """
    n, hid = h.shape
    prev, ctx, mem_before = _tf_ctx(params, placements, node_mask,
                                    mem_frac, comp_frac)
    x = _inputs(params, h, prev, ctx)
    for lp in params["layers"]:
        if use_attention:
            q, k, v = _proj_qkv(lp, x, c, heads)
            if attn_impl == "pallas_band":
                out = kops.causal_window_attention(
                    q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                    v.transpose(1, 0, 2), window=min(window, n),
                    impl="band").transpose(1, 0, 2).reshape(n, hid)
            else:
                out = _banded_attention(q, k, v, window).reshape(n, hid)
            x = x + nn.dense(lp["wo"], modulate(c, out)) * node_mask[:, None]
        x = _ffn(lp, x, c)
    logits = _head_logits(params, x, c, num_devices,
                          _dev_keys(params, dev_feats))
    if incumbent_bias is not None:
        logits = logits + incumbent_bias
    cap = _cap_vector(params, dev_mem_cap) if mask_full else None
    if cap is not None:
        logits = _mask_full_devices(logits, mem_before, mem_frac, cap,
                                    num_devices)
    return logits


# --------------------------------------------------- segmented TF decode
@partial(jax.jit, static_argnames=("heads", "num_devices", "use_attention",
                                   "attn_impl"))
def _tf_segment(params, x, kmem, vmem, node_mask, base, c, dev_keys,
                mem_before, mem_frac, cap, bias, *,
                heads: int, num_devices: int, use_attention: bool,
                attn_impl: str = "jnp"):
    """One teacher-forced segment with Transformer-XL-style memory.

    x: [S, H] decoder inputs; kmem/vmem: [L, W-1, heads, hd] keys/values
    of the previous W-1 positions per layer; base: global index of x[0];
    mem_before/mem_frac/cap: the segment's slice of the memory-aware
    decode mask inputs (cap None disables masking); bias: the segment's
    slice of the incumbent bias (None disables it, tracing the exact
    pre-bias program).
    Returns (logits [S, Dmax], new kmem, new vmem).  The W-wide causal
    band over memory+segment is computed by :func:`_band_attention`: the
    band ``_banded_attention`` gathers from the full sequence, so the
    values are the same up to the dot kernels' rounding at the segment's
    block shape.  ``base`` stays a dynamic operand, so every segment of
    every graph reuses one compiled program per segment config.
    ``attn_impl="pallas_band"`` computes the band through the
    block-sparse kernel instead.
    """
    s, hid = x.shape
    w = kmem.shape[1] + 1
    new_k, new_v = [], []
    for li, lp in enumerate(params["layers"]):
        if use_attention:
            q, k, v = _proj_qkv(lp, x, c, heads)             # [S, heads, hd]
            kbuf = jnp.concatenate([kmem[li], k])            # [W-1+S, ...]
            vbuf = jnp.concatenate([vmem[li], v])
            if attn_impl == "pallas_band":
                out = kops.band_mha_with_memory(
                    q, kbuf, vbuf, base, window=w).reshape(s, hid)
            else:
                out = _band_attention(q, kbuf, vbuf, base, w).reshape(
                    s, hid)
            x = x + nn.dense(lp["wo"], modulate(c, out)) * node_mask[:, None]
            new_k.append(kbuf[s:])
            new_v.append(vbuf[s:])
        else:
            new_k.append(kmem[li])
            new_v.append(vmem[li])
        x = _ffn(lp, x, c)
    logits = _head_logits(params, x, c, num_devices, dev_keys)
    if bias is not None:
        logits = logits + bias
    if cap is not None:
        logits = _mask_full_devices(logits, mem_before, mem_frac, cap,
                                    num_devices)
    return logits, jnp.stack(new_k), jnp.stack(new_v)


def apply_tf_segmented(params: Dict[str, Any], h: jnp.ndarray,
                       node_mask: jnp.ndarray, placements: jnp.ndarray,
                       c: Optional[jnp.ndarray], mem_frac: jnp.ndarray,
                       comp_frac: jnp.ndarray,
                       dev_feats: Optional[jnp.ndarray] = None, *,
                       segment: int = 512, window: int = 256,
                       heads: int = 4, num_devices: int = 4,
                       use_attention: bool = True,
                       dev_mem_cap: Optional[jnp.ndarray] = None,
                       mask_full: bool = False,
                       incumbent_bias: Optional[jnp.ndarray] = None,
                       attn_impl: str = "jnp") -> jnp.ndarray:
    """Teacher-forced logits via fixed-size segments (paper's scalable
    segmented attention): compiled shapes are per-(segment, window), so a
    graph of ANY length reuses one compiled step — a 50k-node GNMT never
    compiles a 50k-shaped program.  ``attn_impl="pallas_band"`` routes
    each segment's band through the block-sparse kernel (tolerance-pinned
    parity vs the default blocked-window band in tier-1).

    Equal to :func:`apply_tf` up to f32 rounding (pinned at 1e-6 by
    tests/test_segmented.py): the causal W-band each node attends to is
    reproduced exactly from the carried per-layer memory of the previous
    ``window - 1`` keys/values; only the dot kernels XLA picks for the
    smaller block shape round differently.
    Memory crossing a segment boundary is ``stop_gradient``-ed
    (Transformer-XL recurrence): forward values are unchanged.  The
    segments run as one ``lax.scan`` over ``[nseg, segment, ...]`` views
    of the padded inputs, carrying the per-layer memory, so under an
    outer jit the whole pass is one program whose size does not grow with
    the node count.  Each segment is rematerialized in the backward pass
    (``jax.checkpoint``), so a gradient holds one segment's activations
    at a time, not the whole graph's: without it every segment's band
    scores and softmax weights, [S, heads, 2W] per layer, would be saved
    for the backward pass together.
    """
    n, hid = h.shape
    pad = (-n) % segment
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        node_mask = jnp.pad(node_mask, (0, pad))
        placements = jnp.pad(placements, (0, pad))
        mem_frac = jnp.pad(mem_frac, (0, pad))
        comp_frac = jnp.pad(comp_frac, (0, pad))
        if incumbent_bias is not None:
            incumbent_bias = jnp.pad(incumbent_bias, ((0, pad), (0, 0)))
    prev, ctx, mem_before = _tf_ctx(params, placements, node_mask,
                                    mem_frac, comp_frac)
    x = _inputs(params, h, prev, ctx)
    dev_keys = _dev_keys(params, dev_feats)
    cap = _cap_vector(params, dev_mem_cap) if mask_full else None
    nlayers = len(params["layers"])
    hd = hid // heads
    nseg = (n + pad) // segment
    kmem = jnp.zeros((nlayers, window - 1, heads, hd))
    vmem = jnp.zeros((nlayers, window - 1, heads, hd))
    # the scan already keeps the forward and the rematerialized backward
    # in separate loop bodies, so CSE barriers would only cost fusion
    step = jax.checkpoint(partial(
        _tf_segment, heads=heads, num_devices=num_devices,
        use_attention=use_attention, attn_impl=attn_impl),
        prevent_cse=False)

    def body(carry, xs):
        kmem, vmem = carry
        x_s, mask_s, mb_s, mf_s, bias_s, base = xs
        logits, kmem, vmem = step(
            params, x_s, jax.lax.stop_gradient(kmem),
            jax.lax.stop_gradient(vmem), mask_s, base, c, dev_keys, mb_s,
            mf_s, cap, bias_s)
        return (kmem, vmem), logits

    def segs(a):
        return None if a is None else a.reshape(nseg, segment, *a.shape[1:])

    _, logits = jax.lax.scan(
        body, (kmem, vmem),
        (segs(x), segs(node_mask), segs(mem_before), segs(mem_frac),
         segs(incumbent_bias), jnp.arange(nseg, dtype=jnp.int32) * segment))
    return logits.reshape(n + pad, -1)[:n]


# ------------------------------------------------------------- AR sampling
def _ar_step_fn(params, c, dev_keys, temperature, *, heads: int,
                num_devices: int, use_attention: bool, cap=None):
    """Build the one-node AR decode step (shared by the monolithic scan
    and the segmented per-segment scan, so both sample identically).

    Carry: (kcache [L,w,heads,hd], vcache, poscache [w], prev_dev,
    mem_used [Dmax], comp_used [Dmax]); xs: (h_i, i, key_i, mem_frac_i,
    comp_frac_i, bias_i).  The ring-buffer width ``w`` is read off the
    carry.  ``cap`` [Dmax] enables the memory-aware decode mask (the
    carried ``mem_used`` accumulator is exactly the TF pass's exclusive
    cumsum, so sampling and ratio evaluation mask identically).
    ``bias_i`` is the node's incumbent-bias row [Dmax], or None — None
    has no pytree leaves, so the unbiased scan is the same program as
    before the bias existed.
    """
    dmax = params["head"]["b"].shape[0]

    def step(carry, xs):
        kc, vc, pc, prev_dev, mem_used, comp_used = carry
        hi, i, ki, mfi, cfi, bi = xs            # [H], idx, rng key, scalars
        hid = hi.shape[0]
        hd = hid // heads
        w = pc.shape[0]
        ctx = jnp.concatenate([mem_used, comp_used, mfi[None], cfi[None]])
        x = _inputs(params, hi[None], prev_dev[None], ctx[None])[0]  # [H]
        slot = jnp.mod(i, w)
        pc_new = jax.lax.dynamic_update_index_in_dim(pc, i, slot, 0)
        valid = (pc_new <= i) & (pc_new > i - w)
        new_kc, new_vc = [], []
        for li, lp in enumerate(params["layers"]):
            if use_attention:
                q, k, v = _proj_qkv(lp, x[None], c, heads)   # [1,heads,hd]
                kci = jax.lax.dynamic_update_index_in_dim(kc[li], k[0], slot, 0)
                vci = jax.lax.dynamic_update_index_in_dim(vc[li], v[0], slot, 0)
                sc = jnp.einsum("hd,whd->hw", q[0], kci) / jnp.sqrt(
                    jnp.float32(hd))
                sc = jnp.where(valid[None, :], sc, NEG)
                aw = jax.nn.softmax(sc, axis=-1)
                out = jnp.einsum("hw,whd->hd", aw, vci).reshape(hid)
                x = x + nn.dense(lp["wo"], modulate(c, out))
                new_kc.append(kci)
                new_vc.append(vci)
            else:
                new_kc.append(kc[li])
                new_vc.append(vc[li])
            x = _ffn(lp, x[None], c)[0]
        logits = _head_logits(params, x[None], c, num_devices, dev_keys)[0]
        if bi is not None:
            logits = logits + bi
        if cap is not None:
            logits = _mask_full_devices(logits, mem_used, mfi, cap,
                                        num_devices)
        logits = logits / jnp.float32(temperature)
        lpv = jax.nn.log_softmax(logits)
        d = jax.random.categorical(ki, logits)
        dev_oh = jax.nn.one_hot(d, dmax)
        mem_new = mem_used + dev_oh * mfi
        comp_new = comp_used + dev_oh * cfi
        return ((jnp.stack(new_kc), jnp.stack(new_vc), pc_new,
                 d.astype(jnp.int32), mem_new, comp_new),
                (d.astype(jnp.int32), lpv[d]))

    return step


def _ar_carry0(params, *, w: int, heads: int, hid: int):
    """Fresh AR decode carry for a ring buffer of width ``w``."""
    hd = hid // heads
    nlayers = len(params["layers"])
    dmax = params["head"]["b"].shape[0]
    return (jnp.zeros((nlayers, w, heads, hd)),
            jnp.zeros((nlayers, w, heads, hd)),
            jnp.full((w,), -10 ** 9, jnp.int32),   # absolute idx per slot
            jnp.int32(dmax), jnp.zeros((dmax,)), jnp.zeros((dmax,)))


def sample_ar(params: Dict[str, Any], h: jnp.ndarray, node_mask: jnp.ndarray,
              c: Optional[jnp.ndarray], key,
              mem_frac: jnp.ndarray, comp_frac: jnp.ndarray,
              dev_feats: Optional[jnp.ndarray] = None, *,
              window: int = 256, heads: int = 4, num_devices: int = 4,
              use_attention: bool = True, temperature: float = 1.0,
              dev_mem_cap: Optional[jnp.ndarray] = None,
              mask_full: bool = False,
              incumbent_bias: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact autoregressive sampling; returns (placement [N], logp [N]).

    Ring-buffer KV caches of size ``window`` per layer reproduce the
    teacher-forced mask exactly (causal, i-j < window, inclusive self);
    per-device mem/comp accumulators reproduce the teacher-forced cumsum.

    ``temperature`` sharpens the per-node device distribution (the serving
    path decodes near-greedily at ~0.1); the returned logp is that of the
    *tempered* distribution, so PPO callers must keep the default 1.0.
    ``mask_full`` enables the memory-aware decode mask (feasible-by-
    construction placements; see ``_mask_full_devices``).
    """
    n, hid = h.shape
    dev_keys = _dev_keys(params, dev_feats)        # loop-invariant
    cap = _cap_vector(params, dev_mem_cap) if mask_full else None
    step = _ar_step_fn(params, c, dev_keys, temperature, heads=heads,
                       num_devices=num_devices, use_attention=use_attention,
                       cap=cap)
    keys = jax.random.split(key, n)
    _, (devs, lps) = jax.lax.scan(
        step, _ar_carry0(params, w=min(window, n), heads=heads, hid=hid),
        (h, jnp.arange(n), keys, mem_frac, comp_frac, incumbent_bias))
    return devs, lps * node_mask


@partial(jax.jit, static_argnames=("heads", "num_devices", "use_attention"))
def _ar_segment_scan(params, h_seg, idx_seg, keys_seg, mf_seg, cf_seg,
                     bias_seg, carry, c, dev_keys, temperature, cap, *,
                     heads: int, num_devices: int, use_attention: bool):
    """Scan the shared AR step over one segment (the ONE compiled decode
    program a segmented sampler reuses for every segment of every graph).
    ``bias_seg`` (incumbent bias slice, or None) is leaf-less when None,
    so the unbiased program is exactly the historical one."""
    step = _ar_step_fn(params, c, dev_keys, temperature, heads=heads,
                       num_devices=num_devices, use_attention=use_attention,
                       cap=cap)
    return jax.lax.scan(step, carry,
                        (h_seg, idx_seg, keys_seg, mf_seg, cf_seg, bias_seg))


# "one program per segment config": every segment of every graph must hit
# these two caches — their counts are exported as gauges and pinned.  The
# TF pass traces ``_tf_segment`` as its scan body, so that cache grows only
# when a segment is dispatched on its own
jaxprof.register("placer.tf_segment", _tf_segment)
jaxprof.register("placer.ar_segment_scan", _ar_segment_scan)


def sample_ar_segmented(params: Dict[str, Any], h: jnp.ndarray,
                        node_mask: jnp.ndarray, c: Optional[jnp.ndarray],
                        key, mem_frac: jnp.ndarray, comp_frac: jnp.ndarray,
                        dev_feats: Optional[jnp.ndarray] = None, *,
                        segment: int = 512, window: int = 256,
                        heads: int = 4, num_devices: int = 4,
                        use_attention: bool = True, temperature: float = 1.0,
                        dev_mem_cap: Optional[jnp.ndarray] = None,
                        mask_full: bool = False,
                        incumbent_bias: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Segment-native AR sampling: a Python loop over fixed-size segments,
    each a single compiled scan of the SAME step function as
    :func:`sample_ar` with the carry threaded through — samples are
    bit-identical to the monolithic scan (tests/test_segmented.py), but
    compiled shapes never exceed ``segment``.

    There is deliberately no ``attn_impl`` here: AR decode is inherently
    sequential (node *i*'s decoder input embeds the device sampled at
    *i-1*), so no parallel attention kernel applies — and the ring-buffer
    KV cache already touches exactly the W-wide band the block-sparse TF
    kernel computes, so there are no wasted bytes to win back.
    """
    n, hid = h.shape
    pad = (-n) % segment
    # per-node keys must match jax.random.split(key, n) exactly for the
    # monolithic pin (split(key, m) has no prefix property in m), so pad
    # the key array instead of splitting wider
    keys = jax.random.split(key, n)
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        mem_frac = jnp.pad(mem_frac, (0, pad))
        comp_frac = jnp.pad(comp_frac, (0, pad))
        keys = jnp.concatenate(
            [keys, jnp.broadcast_to(keys[-1:], (pad,) + keys.shape[1:])])
        if incumbent_bias is not None:
            incumbent_bias = jnp.pad(incumbent_bias, ((0, pad), (0, 0)))
    dev_keys = _dev_keys(params, dev_feats)
    cap = _cap_vector(params, dev_mem_cap) if mask_full else None
    carry = _ar_carry0(params, w=window, heads=heads, hid=hid)
    idx = jnp.arange(n + pad)
    temp = jnp.float32(temperature)
    devs, lps = [], []
    tracer = get_tracer()
    for s0 in range(0, n + pad, segment):
        sl = slice(s0, s0 + segment)
        with tracer.span("placer.ar_segment", cat="placer", seg_start=s0,
                         segment=segment):
            carry, (d_seg, lp_seg) = _ar_segment_scan(
                params, h[sl], idx[sl], keys[sl], mem_frac[sl],
                comp_frac[sl],
                None if incumbent_bias is None else incumbent_bias[sl],
                carry, c, dev_keys, temp, cap, heads=heads,
                num_devices=num_devices, use_attention=use_attention)
        devs.append(d_seg)
        lps.append(lp_seg)
    return (jnp.concatenate(devs)[:n],
            jnp.concatenate(lps)[:n] * node_mask)
