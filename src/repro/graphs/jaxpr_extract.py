"""Trace any JAX computation into the GDP dataflow-graph IR.

``extract(fn, *args)`` jaxpr-traces ``fn`` and emits a
:class:`~repro.core.graph.DataflowGraph` at primitive granularity: one node
per eqn, edges along data dependencies, FLOP/byte costs estimated from
avals.  ``scan``/``while``/``pjit`` calls become fused ``scan`` nodes whose
cost is the traced body cost times the trip count — the same granularity a
TF graph gives the paper after op fusion.

This is the integration point that makes GDP a first-class feature of the
framework: the assigned model-zoo architectures (reduced configs) are traced
through here and placed by the learned policy (see
``examples/place_model_zoo.py`` and ``tests/test_jaxpr_extract.py``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.extend import core as jcore

from repro.core.graph import DataflowGraph, MAX_SHAPE_RANK, op_id, topo_relabel
from repro.obs import jaxprof
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import get_tracer

_PRIM_TO_OP = {
    "dot_general": "matmul",
    "conv_general_dilated": "conv",
    "add": "elementwise", "sub": "elementwise", "mul": "elementwise",
    "div": "elementwise", "max": "elementwise", "min": "elementwise",
    "exp": "elementwise", "log": "elementwise", "tanh": "elementwise",
    "logistic": "elementwise", "rsqrt": "elementwise", "sqrt": "elementwise",
    "pow": "elementwise", "integer_pow": "elementwise", "neg": "elementwise",
    "select_n": "elementwise", "clamp": "elementwise", "sign": "elementwise",
    "erf": "elementwise", "abs": "elementwise", "floor": "elementwise",
    "stop_gradient": "elementwise", "convert_element_type": "elementwise",
    "reduce_sum": "reduce", "reduce_max": "reduce", "reduce_min": "reduce",
    "argmax": "reduce", "argmin": "reduce", "cumsum": "reduce",
    "reduce_and": "reduce", "reduce_or": "reduce",
    "softmax": "softmax", "custom_jvp_call": "other",
    "gather": "gather", "scatter": "scatter", "scatter_add": "scatter",
    "dynamic_slice": "dynamic_slice", "dynamic_update_slice": "scatter",
    "concatenate": "concat", "slice": "split", "transpose": "transpose",
    "reshape": "reshape", "broadcast_in_dim": "reshape", "squeeze": "reshape",
    "iota": "other", "rev": "transpose", "pad": "reshape",
    "scan": "scan", "while": "scan", "pjit": "scan", "jit": "scan",
    "closed_call": "scan",
    "custom_vjp_call": "scan", "remat": "scan", "checkpoint": "scan",
    "all_reduce": "collective", "all_gather": "collective",
    "psum": "collective", "all_to_all": "collective",
    "reduce_scatter": "collective", "ppermute": "collective",
}

# the call primitive is "jit" in the installed jax, "pjit" in older releases
_FUSED = {"scan", "while", "pjit", "jit", "closed_call", "custom_vjp_call",
          "custom_jvp_call", "remat", "checkpoint", "cond"}


def _aval_bytes(aval) -> float:
    try:
        return float(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 4.0


def _aval_shape(aval):
    try:
        return tuple(int(s) for s in aval.shape[:MAX_SHAPE_RANK])
    except Exception:
        return ()


def _eqn_flops(eqn) -> float:
    """FLOP estimate for one primitive from its avals."""
    p = eqn.primitive.name
    outs = sum(float(np.prod(v.aval.shape)) if v.aval.shape else 1.0
               for v in eqn.outvars)
    if p == "dot_general":
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        k = math.prod(lhs[i] for i in lc) if lc else 1
        return 2.0 * outs * k
    if p == "conv_general_dilated":
        rhs = eqn.invars[1].aval.shape  # filter
        return 2.0 * outs * float(np.prod(rhs[:-1]))  # k*k*cin per output
    if p in ("reduce_sum", "reduce_max", "reduce_min", "cumsum"):
        ins = float(np.prod(eqn.invars[0].aval.shape)) if eqn.invars[0].aval.shape else 1.0
        return ins
    return outs  # elementwise-ish: one flop per output element


def _jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _FUSED:
            inner = _inner_jaxpr(eqn)
            if inner is not None:
                body = _jaxpr_flops(inner)
                trips = _trip_count(eqn)
                total += body * trips
                continue
        total += _eqn_flops(eqn)
    return total


def _inner_jaxpr(eqn):
    for key in ("jaxpr", "call_jaxpr", "body_jaxpr", "cond_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is not None:
            return sub.jaxpr if hasattr(sub, "jaxpr") else sub
    for v in eqn.params.values():
        if isinstance(v, jcore.ClosedJaxpr):
            return v.jaxpr
    return None


def _trip_count(eqn) -> float:
    if eqn.primitive.name == "scan":
        return float(eqn.params.get("length", 1))
    return 1.0


class _Accum:
    """Mutable node/edge accumulator shared by the (possibly recursive)
    jaxpr walk."""

    def __init__(self, max_nodes: int):
        self.op_type: List[int] = []
        self.flops: List[float] = []
        self.out_bytes: List[float] = []
        self.mem_bytes: List[float] = []
        self.out_shape: List[tuple] = []
        self.src: List[int] = []
        self.dst: List[int] = []
        self.max_nodes = max_nodes

    def new_node(self, op: str, aval, fl: float, extra_mem: float = 0.0) -> int:
        nid = len(self.op_type)
        if nid >= self.max_nodes:
            raise RuntimeError(
                f"extract: expansion exceeded max_nodes={self.max_nodes}; "
                f"lower `expand` or raise `max_nodes`")
        self.op_type.append(op_id(op))
        self.flops.append(fl)
        b = _aval_bytes(aval)
        self.out_bytes.append(b)
        self.mem_bytes.append(b + extra_mem)
        self.out_shape.append(_aval_shape(aval))
        return nid

    def edge(self, s: int, d: int) -> None:
        if s != d:
            self.src.append(s)
            self.dst.append(d)


def _producers_of(eqn, env: Dict[Any, int]) -> List[int]:
    out = []
    for iv in eqn.invars:
        if isinstance(iv, jcore.Literal):
            continue
        p = env.get(iv)
        if p is not None:
            out.append(p)
    return out


def _fused_node(acc: _Accum, eqn, env: Dict[Any, int]) -> None:
    """Legacy behavior: one ``scan`` node for a fused region, cost =
    traced body cost times the trip count."""
    inner = _inner_jaxpr(eqn)
    fl = (_jaxpr_flops(inner) * _trip_count(eqn)) if inner is not None \
        else _eqn_flops(eqn)
    nid = acc.new_node("scan", eqn.outvars[0].aval, fl,
                       extra_mem=sum(_aval_bytes(v.aval)
                                     for v in eqn.outvars[1:]))
    for p in _producers_of(eqn, env):
        acc.edge(p, nid)
    for ov in eqn.outvars:
        env[ov] = nid


def _bind_inner(acc: _Accum, jaxpr, in_nodes: List[int]) -> Dict[Any, int]:
    """Environment for an inlined inner jaxpr: invars map to the caller's
    producer nodes, constvars become parameter nodes."""
    env: Dict[Any, int] = {}
    for v in jaxpr.constvars:
        env[v] = acc.new_node("parameter", v.aval, 0.0)
    for v, n in zip(jaxpr.invars, in_nodes):
        if n is not None:
            env[v] = n
    return env


def _expand_scan(acc: _Accum, eqn, env: Dict[Any, int],
                 expand: int, depth: int) -> bool:
    """Unroll one scan eqn trip by trip.  Returns False (caller keeps the
    fused node) when the trip count exceeds ``expand`` or the jaxpr
    doesn't look like a canonical scan."""
    inner = _inner_jaxpr(eqn)
    length = int(eqn.params.get("length", 0))
    nc = int(eqn.params.get("num_consts", 0))
    ncar = int(eqn.params.get("num_carry", 0))
    if (inner is None or length <= 0 or length > expand
            or len(inner.invars) != len(eqn.invars)
            or len(inner.outvars) < ncar):
        return False
    nxs = len(eqn.invars) - nc - ncar
    const_nodes = [env.get(iv) if not isinstance(iv, jcore.Literal) else None
                   for iv in eqn.invars[:nc]]
    carry_nodes = [env.get(iv) if not isinstance(iv, jcore.Literal) else None
                   for iv in eqn.invars[nc:nc + ncar]]
    xs_nodes = [env.get(iv) if not isinstance(iv, jcore.Literal) else None
                for iv in eqn.invars[nc + ncar:]]
    ys_vars = eqn.outvars[ncar:]
    ys_trip_nodes: List[List[int]] = [[] for _ in ys_vars]

    for t in range(length):
        # per-trip xs slices: a "split" node per scanned operand, so the
        # edge into the body carries element bytes, not the stacked array
        x_nodes: List[Any] = []
        for j, xn in enumerate(xs_nodes):
            xv = inner.invars[nc + ncar + j]
            if xn is None:
                x_nodes.append(None)
                continue
            sl = acc.new_node("split", xv.aval, 0.0)
            acc.edge(xn, sl)
            x_nodes.append(sl)
        trip_env = _bind_inner(acc, inner,
                               const_nodes + carry_nodes + x_nodes)
        _walk(acc, inner, trip_env, expand, depth + 1)
        carry_nodes = [trip_env.get(ov) if not isinstance(ov, jcore.Literal)
                       else None for ov in inner.outvars[:ncar]]
        for j, ov in enumerate(inner.outvars[ncar:ncar + len(ys_vars)]):
            if not isinstance(ov, jcore.Literal) and ov in trip_env:
                ys_trip_nodes[j].append(trip_env[ov])

    for v, n in zip(eqn.outvars[:ncar], carry_nodes):
        if n is not None:
            env[v] = n
    for v, trips in zip(ys_vars, ys_trip_nodes):
        cat = acc.new_node("concat", v.aval, 0.0)
        for n in trips:
            acc.edge(n, cat)
        env[v] = cat
    return True


def _expand_call(acc: _Accum, eqn, env: Dict[Any, int],
                 expand: int, depth: int) -> bool:
    """Inline a call-like fused eqn (pjit / remat / custom_*_call /
    closed_call) once.  Returns False on shape mismatch (caller keeps
    the fused node)."""
    inner = _inner_jaxpr(eqn)
    if inner is None or len(inner.invars) != len(eqn.invars):
        return False
    in_nodes = [env.get(iv) if not isinstance(iv, jcore.Literal) else None
                for iv in eqn.invars]
    sub_env = _bind_inner(acc, inner, in_nodes)
    _walk(acc, inner, sub_env, expand, depth + 1)
    if len(inner.outvars) != len(eqn.outvars):
        return False
    for v, ov in zip(eqn.outvars, inner.outvars):
        if not isinstance(ov, jcore.Literal) and ov in sub_env:
            env[v] = sub_env[ov]
    return True


_MAX_EXPAND_DEPTH = 12

# fused primitives the expander can see through (`while`/`cond` trip
# structure is data-dependent — they always stay fused).  "remat2" is
# jax's current checkpoint primitive: the legacy fused path predates it
# and treats it as a plain node (kept bit-identical), but expansion must
# inline it or every layer body stays hidden inside the checkpoint.
_EXPANDABLE = {"scan", "pjit", "jit", "closed_call", "custom_vjp_call",
               "custom_jvp_call", "remat", "checkpoint", "remat2"}


def _walk(acc: _Accum, jaxpr, env: Dict[Any, int],
          expand, depth: int) -> None:
    for eqn in jaxpr.eqns:
        if not eqn.outvars:
            # nothing downstream can read it (grad leaves calls whose
            # every output was dead code with their outputs pruned)
            continue
        pname = eqn.primitive.name
        if pname in _FUSED or (expand and pname in _EXPANDABLE):
            if (expand and depth < _MAX_EXPAND_DEPTH
                    and pname in _EXPANDABLE):
                done = (_expand_scan(acc, eqn, env, expand, depth)
                        if pname == "scan"
                        else _expand_call(acc, eqn, env, expand, depth))
                if done:
                    continue
            _fused_node(acc, eqn, env)
            continue
        op = _PRIM_TO_OP.get(pname, "other")
        nid = acc.new_node(op, eqn.outvars[0].aval, _eqn_flops(eqn),
                           extra_mem=sum(_aval_bytes(v.aval)
                                         for v in eqn.outvars[1:]))
        for p in _producers_of(eqn, env):
            acc.edge(p, nid)
        for ov in eqn.outvars:
            env[ov] = nid


def extract(fn: Callable, *args, name: str = "jaxpr",
            expand: Optional[int] = None, max_nodes: int = 2_000_000,
            **kwargs) -> DataflowGraph:
    """Trace ``fn`` and emit a :class:`DataflowGraph`.

    Default (``expand=None``) is the historical fused granularity:
    ``scan``/``while``/``pjit`` become single ``scan`` nodes with cost =
    body cost × trip count.  With ``expand=T`` the extractor *inlines*
    fused regions instead — call-like primitives (pjit / remat /
    custom_* / closed_call) are inlined in place and every ``scan``
    whose trip count is ≤ ``T`` is unrolled trip by trip (per-trip
    ``split`` slice nodes on the scanned operands, per-output ``concat``
    collectors, carries chained across trips); deeper scans stay fused.
    That is how the jumbo configs in ``src/repro/configs`` become
    500k+-node graphs for the hierarchical pipeline.  Arguments may be
    ``jax.ShapeDtypeStruct``s — nothing is materialized."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    jaxpr = closed.jaxpr

    acc = _Accum(max_nodes)
    env: Dict[Any, int] = {}
    for v in jaxpr.constvars:
        env[v] = acc.new_node("parameter", v.aval, 0.0)
    for v in jaxpr.invars:
        env[v] = acc.new_node("input", v.aval, 0.0)
    _walk(acc, jaxpr, env, expand, 0)

    shp = np.zeros((len(acc.op_type), MAX_SHAPE_RANK), dtype=np.int64)
    for i, s in enumerate(acc.out_shape):
        shp[i, :len(s)] = s
    # dedupe parallel edges
    if acc.src:
        pairs = np.unique(np.stack([acc.src, acc.dst], 1), axis=0)
        src_a, dst_a = pairs[:, 0], pairs[:, 1]
    else:
        src_a = np.zeros(0, np.int64)
        dst_a = np.zeros(0, np.int64)
    if expand and (src_a.size == 0 or np.all(src_a < dst_a)):
        # nodes were emitted in dataflow order, so creation order IS a
        # topological order — skip the O(N+E) python Kahn pass, which
        # dominates wall time at 500k+ nodes.  (The fused path keeps
        # topo_relabel for bit-identical node orders vs historical runs.)
        g = DataflowGraph(
            name=name, op_type=np.asarray(acc.op_type, np.int32),
            flops=np.asarray(acc.flops, np.float64),
            out_bytes=np.asarray(acc.out_bytes, np.float64),
            mem_bytes=np.asarray(acc.mem_bytes, np.float64),
            out_shape=shp, src=src_a.astype(np.int32),
            dst=dst_a.astype(np.int32))
        g.validate()
        return g
    return topo_relabel(name, acc.op_type, acc.flops, acc.out_bytes,
                        acc.mem_bytes, shp, src_a, dst_a)


# ---------------------------------------------------------------------------
# Model-zoo extraction with a content-addressed disk cache.
# ---------------------------------------------------------------------------
CACHE_ENV = "REPRO_JAXPR_CACHE"
# beside the compile cache, <repo>/.cache/jaxprs (gitignored)
_DEFAULT_CACHE = os.path.join(os.path.dirname(jaxprof.REPO_CACHE_DIR),
                              "jaxprs")

# graph_extract_total{result=hit|miss}: extract_arch calls by disk-cache
# result; graph_extract_nodes: the node count of the last graph returned
METRICS = MetricsRegistry()
_EXTRACTS = METRICS.counter("graph_extract_total",
                            "model-zoo graph extractions by disk-cache result",
                            ("result",))
_NODES = METRICS.gauge("graph_extract_nodes",
                       "nodes of the last extracted model-zoo graph")


def _arch_config(arch_name: str, reduced: bool, layers: Optional[int]):
    from repro.configs import get_config, get_reduced
    cfg = get_reduced(arch_name) if reduced else get_config(arch_name)
    return cfg if layers is None else cfg.first_layers(layers)


def arch_digest(arch_name: str, *, reduced: bool = False,
                mode: str = "loss", seq: Optional[int] = None,
                batch: int = 8, expand: Optional[int] = None,
                layers: Optional[int] = None) -> str:
    """Stable hash of everything that determines an extracted arch graph:
    the full :class:`~repro.configs.base.ArchConfig` contents (cut to
    ``layers``) plus the trace shape and expansion settings.  Repeated
    campaign runs key the disk cache on this, so a config edit re-traces
    and a rerun doesn't."""
    cfg = _arch_config(arch_name, reduced, layers)
    payload = json.dumps(
        {"cfg": dataclasses.asdict(cfg), "reduced": reduced, "mode": mode,
         "seq": seq, "batch": batch, "expand": expand, "v": 3},
        sort_keys=True, default=str)
    return hashlib.sha256(payload.encode()).hexdigest()


def _graph_to_npz(g: DataflowGraph, path: str) -> None:
    np.savez_compressed(path, name=np.array(g.name), op_type=g.op_type,
                        flops=g.flops, out_bytes=g.out_bytes,
                        mem_bytes=g.mem_bytes, out_shape=g.out_shape,
                        src=g.src, dst=g.dst)


def _graph_from_npz(path: str) -> DataflowGraph:
    with np.load(path) as z:
        g = DataflowGraph(name=str(z["name"]), op_type=z["op_type"],
                          flops=z["flops"], out_bytes=z["out_bytes"],
                          mem_bytes=z["mem_bytes"], out_shape=z["out_shape"],
                          src=z["src"], dst=z["dst"])
    g.validate()
    return g


def _batch_avals(cfg, batch: int, seq: int) -> Dict[str, Any]:
    """The training batch's avals, frontend stubs included (whisper's
    frames, qwen2-vl's patches and M-RoPE positions), with the patches cut
    to ``seq`` where the sequence is shorter."""
    from repro.configs.base import ShapeConfig
    from repro.launch.input_specs import train_batch_specs
    out = train_batch_specs(cfg, ShapeConfig("extract", seq, batch, "train"))
    pe = out.get("patch_embeds")
    if pe is not None and pe.shape[1] > seq:
        out["patch_embeds"] = jax.ShapeDtypeStruct(
            (batch, seq, pe.shape[2]), pe.dtype)
    return out


def extract_arch(arch_name: str, *, reduced: bool = False,
                 mode: str = "loss", seq: Optional[int] = None,
                 batch: int = 8, expand: Optional[int] = None,
                 cache_dir: Optional[str] = None,
                 max_nodes: int = 2_000_000,
                 layers: Optional[int] = None) -> DataflowGraph:
    """Extract a model-zoo architecture's dataflow graph, disk-cached.

    ``mode`` is ``"loss"`` (forward + loss) or ``"grad"`` (forward +
    backward: ``jax.grad`` of the loss — roughly 3× the nodes).  ``seq``
    overrides the trace sequence length (default: the arch's trained
    seq, 4096); ``batch`` is the traced global batch (node count is
    batch-independent — only per-node costs scale).  Tracing uses
    ``jax.eval_shape``/``ShapeDtypeStruct`` throughout, so a 398B-param
    config costs abstract shapes, not memory.

    ``layers`` keeps the first layers of the architecture, which must be
    whole periods (:meth:`~repro.configs.base.ArchConfig.first_layers`).

    Results are cached under ``cache_dir`` (default ``$REPRO_JAXPR_CACHE``
    or ``<repo>/.cache/jaxprs``) keyed by :func:`arch_digest` — re-running
    a jumbo campaign never re-traces an unchanged config.  Each call opens
    the span ``graph.extract`` and counts ``graph_extract_total{result}``
    in :data:`METRICS`.
    """
    if mode not in ("loss", "grad"):
        raise ValueError(f"extract_arch: unknown mode {mode!r}")
    cfg = _arch_config(arch_name, reduced, layers)
    s = int(seq if seq is not None else 4096)
    with get_tracer().span("graph.extract", cat="graph", arch=arch_name,
                           mode=mode, seq=s, layers=cfg.n_layers,
                           expand=expand) as sp:
        digest = arch_digest(arch_name, reduced=reduced, mode=mode, seq=seq,
                             batch=batch, expand=expand, layers=layers)
        cache_dir = cache_dir or os.environ.get(CACHE_ENV, _DEFAULT_CACHE)
        path = os.path.join(cache_dir, f"{arch_name}-{digest[:16]}.npz")
        hit = os.path.exists(path)
        g = (_graph_from_npz(path) if hit else
             _trace_arch(cfg, arch_name, reduced, mode, s, batch, expand,
                         max_nodes, cache_dir, path))
        sp.set(nodes=g.num_nodes, cache_hit=hit)
    _EXTRACTS.inc(result="hit" if hit else "miss")
    _NODES.set(g.num_nodes)
    return g


def _trace_arch(cfg, arch_name, reduced, mode, s, batch, expand, max_nodes,
                cache_dir, path) -> DataflowGraph:
    """Trace ``cfg``'s loss or gradient and store the graph at ``path``."""
    from repro.models.model import build_model
    model = build_model(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    batch_avals = _batch_avals(cfg, batch, s)
    fn = model.loss if mode == "loss" else (
        lambda p, b: jax.grad(model.loss)(p, b))
    name = f"{arch_name}{'-r' if reduced else ''}-{mode}-s{s}"
    g = extract(fn, params, batch_avals, name=name, expand=expand,
                max_nodes=max_nodes)
    os.makedirs(cache_dir, exist_ok=True)
    tmp = path[:-len(".npz")] + ".tmp.npz"   # np.savez appends .npz itself
    _graph_to_npz(g, tmp)
    os.replace(tmp, path)
    return g
