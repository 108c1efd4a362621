"""Synthetic dataflow-graph families matching the paper's workloads.

The paper evaluates on RNNLM, GNMT, Transformer-XL, Inception-V3, AmoebaNet
and WaveNet at several depths (Table 1).  These generators produce dataflow
graphs at TF-op granularity: recurrent cells are decomposed into their
primitive matmuls/activations and unrolled over time, attention into its
constituent ops, convolutions into per-module branches.  FLOP/byte costs are
sized so that the simulator's step times land in the paper's regime
(0.2–1.0 s on P100-class devices).

All generators accept ``time_steps``/``scale`` so tests use small instances
while benchmarks can reproduce paper-scale node counts (8-layer GNMT with
``time_steps=128`` exceeds 50k nodes).

``model_step`` is the one family that is traced, not generated: a
model-zoo architecture's training step, extracted from its JAX program.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core.graph import DataflowGraph, GraphBuilder

F32 = 4


# --------------------------------------------------------------------------
# LSTM-based families
# --------------------------------------------------------------------------
def _lstm_cell(b: GraphBuilder, x: int, h: int, params: Sequence[int],
               batch: int, d: int) -> int:
    """Decomposed LSTM cell: 2 matmuls + gate nonlinearities (10 ops)."""
    wx, wh = params
    mm_flops = 2.0 * batch * d * 4 * d
    gx = b.add("matmul", (batch, 4 * d), flops=mm_flops, deps=[x, wx])
    gh = b.add("matmul", (batch, 4 * d), flops=mm_flops, deps=[h, wh])
    gates = b.add("elementwise", (batch, 4 * d), flops=batch * 4 * d, deps=[gx, gh])
    i = b.add("elementwise", (batch, d), flops=batch * d, deps=[gates])
    f = b.add("elementwise", (batch, d), flops=batch * d, deps=[gates])
    o = b.add("elementwise", (batch, d), flops=batch * d, deps=[gates])
    g = b.add("elementwise", (batch, d), flops=batch * d, deps=[gates])
    c = b.add("elementwise", (batch, d), flops=3 * batch * d, deps=[i, f, g])
    hout = b.add("elementwise", (batch, d), flops=2 * batch * d, deps=[o, c])
    return hout


def rnnlm(layers: int = 2, time_steps: int = 32, batch: int = 128,
          d: int = 1024, vocab: int = 32000) -> DataflowGraph:
    b = GraphBuilder(f"rnnlm-{layers}")
    emb_w = b.param((vocab, d))
    layer_params = [(b.param((d, 4 * d)), b.param((d, 4 * d))) for _ in range(layers)]
    soft_w = b.param((d, vocab))
    h_prev = [b.add("input", (batch, d)) for _ in range(layers)]
    losses: List[int] = []
    for t in range(time_steps):
        x = b.add("embedding", (batch, d), flops=batch * d, deps=[emb_w])
        for l in range(layers):
            x = _lstm_cell(b, x, h_prev[l], layer_params[l], batch, d)
            h_prev[l] = x
        logits = b.add("matmul", (batch, vocab), flops=2.0 * batch * d * vocab,
                       deps=[x, soft_w])
        losses.append(b.add("softmax", (batch, vocab), flops=5.0 * batch * vocab,
                            deps=[logits]))
    b.add("loss", (1,), flops=batch * time_steps, deps=losses[-4:])
    return b.build()


def gnmt(layers: int = 2, time_steps: int = 24, batch: int = 128,
         d: int = 1024, vocab: int = 32000) -> DataflowGraph:
    """Encoder(biLSTM first layer)-decoder with per-step attention."""
    b = GraphBuilder(f"gnmt-{layers}")
    emb_w = b.param((vocab, d))
    enc_params = [(b.param((d, 4 * d)), b.param((d, 4 * d))) for _ in range(layers)]
    dec_params = [(b.param((d, 4 * d)), b.param((d, 4 * d))) for _ in range(layers)]
    attn_w = b.param((d, d))
    soft_w = b.param((d, vocab))

    # encoder
    enc_h = [b.add("input", (batch, d)) for _ in range(layers)]
    enc_outs: List[int] = []
    for t in range(time_steps):
        x = b.add("embedding", (batch, d), flops=batch * d, deps=[emb_w])
        for l in range(layers):
            x = _lstm_cell(b, x, enc_h[l], enc_params[l], batch, d)
            enc_h[l] = x
        enc_outs.append(x)
    enc_cat = b.add("concat", (batch, time_steps, d), deps=enc_outs[-8:])

    # decoder with attention each step
    dec_h = [b.add("input", (batch, d)) for _ in range(layers)]
    last = None
    for t in range(time_steps):
        x = b.add("embedding", (batch, d), flops=batch * d, deps=[emb_w])
        for l in range(layers):
            x = _lstm_cell(b, x, dec_h[l], dec_params[l], batch, d)
            dec_h[l] = x
        q = b.add("matmul", (batch, d), flops=2.0 * batch * d * d, deps=[x, attn_w])
        sc = b.add("matmul", (batch, time_steps), flops=2.0 * batch * time_steps * d,
                   deps=[q, enc_cat])
        aw = b.add("softmax", (batch, time_steps), flops=5.0 * batch * time_steps, deps=[sc])
        ctx = b.add("matmul", (batch, d), flops=2.0 * batch * time_steps * d,
                    deps=[aw, enc_cat])
        x = b.add("elementwise", (batch, d), flops=batch * d, deps=[x, ctx])
        logits = b.add("matmul", (batch, vocab), flops=2.0 * batch * d * vocab,
                       deps=[x, soft_w])
        last = b.add("softmax", (batch, vocab), flops=5.0 * batch * vocab, deps=[logits])
    b.add("loss", (1,), flops=batch, deps=[last])
    return b.build()


# --------------------------------------------------------------------------
# Transformer-XL
# --------------------------------------------------------------------------
def transformer_xl(layers: int = 2, segments: int = 8, batch: int = 32,
                   d: int = 1024, heads: int = 16, seg_len: int = 256,
                   vocab: int = 32000) -> DataflowGraph:
    b = GraphBuilder(f"transformer_xl-{layers}")
    emb_w = b.param((vocab, d))
    lp = []
    for _ in range(layers):
        lp.append(dict(
            wqkv=b.param((d, 3 * d)), wo=b.param((d, d)),
            w1=b.param((d, 4 * d)), w2=b.param((4 * d, d)),
        ))
    soft_w = b.param((d, vocab))
    tok = batch * seg_len
    mem: List[int] = [b.add("input", (batch, seg_len, d)) for _ in range(layers)]
    last = None
    for s in range(segments):
        x = b.add("embedding", (batch, seg_len, d), flops=tok * d, deps=[emb_w])
        for l in range(layers):
            p = lp[l]
            qkv = b.add("matmul", (batch, seg_len, 3 * d), flops=2.0 * tok * d * 3 * d,
                        deps=[x, p["wqkv"]])
            kv = b.add("concat", (batch, 2 * seg_len, d), deps=[qkv, mem[l]])
            sc = b.add("matmul", (batch, heads, seg_len, 2 * seg_len),
                       flops=2.0 * batch * heads * seg_len * 2 * seg_len * (d // heads),
                       deps=[qkv, kv])
            aw = b.add("softmax", (batch, heads, seg_len, 2 * seg_len),
                       flops=5.0 * batch * heads * seg_len * 2 * seg_len, deps=[sc])
            av = b.add("matmul", (batch, seg_len, d),
                       flops=2.0 * batch * heads * seg_len * 2 * seg_len * (d // heads),
                       deps=[aw, kv])
            ao = b.add("matmul", (batch, seg_len, d), flops=2.0 * tok * d * d,
                       deps=[av, p["wo"]])
            x1 = b.add("layernorm", (batch, seg_len, d), flops=8.0 * tok * d, deps=[x, ao])
            f1 = b.add("matmul", (batch, seg_len, 4 * d), flops=2.0 * tok * d * 4 * d,
                       deps=[x1, p["w1"]])
            f1a = b.add("elementwise", (batch, seg_len, 4 * d), flops=tok * 4 * d, deps=[f1])
            f2 = b.add("matmul", (batch, seg_len, d), flops=2.0 * tok * 4 * d * d,
                       deps=[f1a, p["w2"]])
            x = b.add("layernorm", (batch, seg_len, d), flops=8.0 * tok * d, deps=[x1, f2])
            mem[l] = x
        logits = b.add("matmul", (batch, seg_len, vocab), flops=2.0 * tok * d * vocab,
                       deps=[x, soft_w])
        last = b.add("softmax", (batch, seg_len, vocab), flops=5.0 * tok * vocab,
                     deps=[logits])
    b.add("loss", (1,), flops=tok, deps=[last])
    return b.build()


# --------------------------------------------------------------------------
# Conv families
# --------------------------------------------------------------------------
def _conv(b: GraphBuilder, x: int, w: int, n: int, cin: int, cout: int,
          hw: int, k: int = 3) -> int:
    flops = 2.0 * n * hw * hw * cin * cout * k * k
    c = b.add("conv", (n, hw, hw, cout), flops=flops, deps=[x, w])
    return b.add("elementwise", (n, hw, hw, cout), flops=float(n * hw * hw * cout),
                 deps=[c])


def inception(batch: int = 64, base: int = 64, modules: int = 9) -> DataflowGraph:
    b = GraphBuilder("inception")
    hw, cin = 73, base
    x = b.add("input", (batch, 147, 147, 32))
    w0 = b.param((3, 3, 32, base))
    x = _conv(b, x, w0, batch, 32, base, hw)
    for m in range(modules):
        cout = base * (1 + m // 3)
        branches = []
        for br, k in enumerate((1, 3, 5)):
            w1 = b.param((1, 1, cin, cout // 2))
            y = _conv(b, x, w1, batch, cin, cout // 2, hw, 1)
            if k > 1:
                w2 = b.param((k, k, cout // 2, cout))
                y = _conv(b, y, w2, batch, cout // 2, cout, hw, k)
            else:
                w2 = b.param((1, 1, cout // 2, cout))
                y = _conv(b, y, w2, batch, cout // 2, cout, hw, 1)
            branches.append(y)
        p = b.add("pool", (batch, hw, hw, cin), flops=float(batch * hw * hw * cin * 9),
                  deps=[x])
        wp = b.param((1, 1, cin, cout))
        branches.append(_conv(b, p, wp, batch, cin, cout, hw, 1))
        x = b.add("concat", (batch, hw, hw, 4 * cout), deps=branches)
        cin = 4 * cout
        if m % 3 == 2 and hw > 9:
            hw = hw // 2
            x = b.add("pool", (batch, hw, hw, cin),
                      flops=float(batch * hw * hw * cin * 9), deps=[x])
    x = b.add("pool", (batch, 1, 1, cin), flops=float(batch * cin * hw * hw), deps=[x])
    wf = b.param((cin, 1000))
    lg = b.add("matmul", (batch, 1000), flops=2.0 * batch * cin * 1000, deps=[x, wf])
    sm = b.add("softmax", (batch, 1000), flops=5.0 * batch * 1000, deps=[lg])
    b.add("loss", (1,), flops=batch, deps=[sm])
    return b.build()


def amoebanet(batch: int = 64, cells: int = 12, filters: int = 96) -> DataflowGraph:
    """NAS cell with 5 pairwise-combine blocks per cell (AmoebaNet-style)."""
    b = GraphBuilder("amoebanet")
    hw = 56
    x_prev = b.add("input", (batch, hw, hw, filters))
    x = b.add("input", (batch, hw, hw, filters))
    f = filters
    for c in range(cells):
        if c % 4 == 3 and hw > 7:
            hw //= 2
            f *= 2
            x = b.add("pool", (batch, hw, hw, f), flops=float(batch * hw * hw * f * 9),
                      deps=[x])
            x_prev = b.add("pool", (batch, hw, hw, f),
                           flops=float(batch * hw * hw * f * 9), deps=[x_prev])
        hidden = [x_prev, x]
        for blk in range(5):
            a = hidden[(blk * 2) % len(hidden)]
            bb = hidden[(blk * 2 + 1) % len(hidden)]
            k = (3, 5, 3, 1, 3)[blk]
            wa = b.param((k, k, f, f))
            ya = _conv(b, a, wa, batch, f, f, hw, k)
            yb = b.add("pool", (batch, hw, hw, f), flops=float(batch * hw * hw * f * 9),
                       deps=[bb])
            hidden.append(b.add("elementwise", (batch, hw, hw, f),
                                flops=float(batch * hw * hw * f), deps=[ya, yb]))
        x_prev, x = x, b.add("concat", (batch, hw, hw, f), deps=hidden[2:])
    x = b.add("pool", (batch, 1, 1, f), flops=float(batch * f * hw * hw), deps=[x])
    wf = b.param((f, 1000))
    lg = b.add("matmul", (batch, 1000), flops=2.0 * batch * f * 1000, deps=[x, wf])
    b.add("loss", (1,), flops=batch, deps=[lg])
    return b.build()


def wavenet(stacks: int = 2, layers_per_stack: int = 18, batch: int = 8,
            channels: int = 256, t: int = 4096) -> DataflowGraph:
    b = GraphBuilder(f"wavenet-{stacks}x{layers_per_stack}")
    x = b.add("input", (batch, t, channels))
    skips: List[int] = []
    for s in range(stacks):
        for l in range(layers_per_stack):
            wf = b.param((2, channels, channels))
            wg = b.param((2, channels, channels))
            cf = b.add("conv", (batch, t, channels),
                       flops=2.0 * batch * t * channels * channels * 2, deps=[x, wf])
            cg = b.add("conv", (batch, t, channels),
                       flops=2.0 * batch * t * channels * channels * 2, deps=[x, wg])
            tf_ = b.add("elementwise", (batch, t, channels),
                        flops=float(batch * t * channels), deps=[cf])
            sg = b.add("elementwise", (batch, t, channels),
                       flops=float(batch * t * channels), deps=[cg])
            z = b.add("elementwise", (batch, t, channels),
                      flops=float(batch * t * channels), deps=[tf_, sg])
            wr = b.param((1, channels, channels))
            r = b.add("conv", (batch, t, channels),
                      flops=2.0 * batch * t * channels * channels, deps=[z, wr])
            x = b.add("elementwise", (batch, t, channels),
                      flops=float(batch * t * channels), deps=[x, r])
            ws = b.param((1, channels, channels))
            skips.append(b.add("conv", (batch, t, channels),
                               flops=2.0 * batch * t * channels * channels, deps=[z, ws]))
    agg = b.add("elementwise", (batch, t, channels),
                flops=float(batch * t * channels * len(skips)), deps=skips[-16:])
    wo = b.param((channels, 256))
    lg = b.add("matmul", (batch, t, 256), flops=2.0 * batch * t * channels * 256,
               deps=[agg, wo])
    sm = b.add("softmax", (batch, t, 256), flops=5.0 * batch * t * 256, deps=[lg])
    b.add("loss", (1,), flops=batch, deps=[sm])
    return b.build()


def model_step(arch: str, mode: str = "grad", seq: int = 4096,
               batch: int = 8, layers: Optional[int] = None,
               expand: Optional[int] = None,
               reduced: bool = False) -> DataflowGraph:
    """A model-zoo architecture's training step (``mode="grad"``: forward
    and backward; ``"loss"``: forward and loss) as a dataflow graph, traced
    through :func:`~repro.graphs.jaxpr_extract.extract_arch` and its disk
    cache.  ``layers`` keeps the first layers (whole periods); ``expand``
    unrolls scans of up to that many trips; ``reduced`` takes the
    architecture's tiny test widths."""
    from repro.graphs.jaxpr_extract import extract_arch
    return extract_arch(arch, reduced=reduced, mode=mode, seq=seq,
                        batch=batch, expand=expand, layers=layers)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------
FAMILIES: Dict[str, Callable[..., DataflowGraph]] = {
    "rnnlm": rnnlm,
    "gnmt": gnmt,
    "transformer_xl": transformer_xl,
    "inception": inception,
    "amoebanet": amoebanet,
    "wavenet": wavenet,
    "model_step": model_step,
}


def make_graph(spec: str, **kw) -> DataflowGraph:
    """``make_graph("gnmt:4")`` -> 4-layer GNMT.  Extra kwargs forwarded."""
    if ":" in spec:
        fam, arg = spec.split(":", 1)
    else:
        fam, arg = spec, None
    fn = FAMILIES[fam]
    if arg is not None:
        if fam == "wavenet":
            stacks = int(arg)
            return fn(stacks=stacks, layers_per_stack=18 * stacks // 2 if stacks > 2 else 18, **kw)
        return fn(int(arg), **kw)
    return fn(**kw)


def paper_suite(small: bool = True) -> List[DataflowGraph]:
    """The paper's Table-1 workload list (small=True shrinks unroll lengths)."""
    ts = 12 if small else 64
    seg = 4 if small else 12
    return [
        rnnlm(2, time_steps=ts), rnnlm(4, time_steps=ts),
        gnmt(2, time_steps=ts), gnmt(4, time_steps=ts), gnmt(8, time_steps=ts),
        transformer_xl(2, segments=seg), transformer_xl(4, segments=seg),
        transformer_xl(8, segments=seg),
        inception(modules=6 if small else 9),
        amoebanet(cells=8 if small else 12),
        wavenet(2, 18 if not small else 9),
        wavenet(4, 18 if not small else 9),
    ]
