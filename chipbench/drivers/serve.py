"""Driver: open-loop placement requests to the program's serving worker.

Set-up makes the window's graphs from the mix and the seed, the weights
from the seed, and a ``PlacementService`` on the wall clock; it serves
the mix's warm-up graphs (one per node bucket) on a first service, so
every program the window uses is compiled, then starts the window on a
fresh service with an empty cache.  The window submits each request at
its due time and polls ``step()`` between due times; after the window
closes it keeps polling until every request is answered or a minute has
passed.  A request's latency runs from its due time to its answer; one
shed or never answered counts as missing any limit.  Each end-to-end
metric ``serve_p<q>_ms`` the cell reports is the ``q``-th percentile of
the latencies of all requests due in the window.

``correct`` takes a sample of the answered requests, drawn from the seed
with the largest among them, and checks each against the plain
reference: the per-node log-probs the decode gave its sampled placements
(tempered as served), the served placement's makespan and validity, and
that the served placement is the best valid sample.
"""
from __future__ import annotations

import gc
import re
import time
from functools import partial
from typing import Any, Dict, List

import numpy as np

from chipbench import flops, harness, trace, traffic
from chipbench.drivers import common
from chipbench.reference import features, policy as ref, sim as ref_sim


class Taps:
    """Keeps what the timed path produced, for the check after the window:
    the batched policy call's placements and log-probs (wrapped where the
    service looks the call up) and, per request, the rows it was given."""

    def __init__(self, service_module):
        self.mod = service_module
        self.fn = service_module._sample_batch_jit
        self.calls: List[Dict[str, Any]] = []
        self.by_req: Dict[int, tuple] = {}
        service_module._sample_batch_jit = self._sample

    def _sample(self, *args, **kwargs):
        out = self.fn(*args, **kwargs)
        self.calls.append({"t": time.perf_counter(), "out": out, "row": 0,
                           "nodes": []})
        return out

    def watch(self, svc) -> None:
        inner = svc._serve_zero_shot

        def serve_zero_shot(req, sampled):
            call = self.calls[-1]
            self.by_req[req.req_id] = (sampled, call["out"][1], call["row"])
            call["row"] += 1
            call["nodes"].append(req.graph.num_nodes)
            inner(req, sampled)

        svc._serve_zero_shot = serve_zero_shot

    def close(self) -> None:
        self.mod._sample_batch_jit = self.fn


def serve_config(cfg: Dict[str, Any], seed: int):
    from repro.serve.service import ServeConfig
    s = cfg["serve"]
    return ServeConfig(cache_capacity=s["cache_capacity"],
                       cache_policy=s["cache_policy"],
                       max_batch=s["max_batch"], max_wait_s=s["max_wait_s"],
                       num_samples=s["num_samples"],
                       temperature=s["temperature"],
                       escalate_margin=s["escalate_margin"],
                       finetune_iters=s["finetune_iters"],
                       max_deg=s["max_deg"], seed=seed)


def window(svc, topo, reqs, graphs, seconds: float, capture=None):
    """Submit each request at its due time; poll between.  With a
    ``capture``, trace from a quarter of the window for up to 5 s.
    Returns the window's start on the service clock, the requests and
    the generator's lateness."""
    n = len(reqs)
    clock = svc.clock
    out, late = [], []
    span = (seconds / 4, seconds / 4 + min(5.0, seconds / 4))
    tracing = False
    t0 = clock.now()
    i = 0
    while True:
        now = clock.now() - t0
        if capture is not None and not tracing and now >= span[0]:
            capture.__enter__()
            tracing = True
        elif tracing and now >= span[1]:
            capture.__exit__(None, None, None)
            tracing, capture = False, None
        if i < n and reqs[i][0] <= now:
            out.append(svc.submit(graphs[i], topo))
            late.append(now - reqs[i][0])
            i += 1
            continue
        done = i == n and all(r.done_t is not None for r in out)
        if (done and now >= seconds and capture is None) or \
                now >= seconds + 60.0:
            break
        svc.step()
        wait = reqs[i][0] - (clock.now() - t0) if i < n else 1e-3
        if wait > 0:
            time.sleep(min(wait, 1e-3))
    return t0, out, late


def summarize(reqs, done, t0: float, seconds: float):
    """(latency per request due in the window, failed, answered in the
    window).  Latency runs from the due time to the answer; a request
    shed, answered without a finite makespan or never answered is +inf,
    so it misses any limit."""
    lat, failed, in_window = [], 0, 0
    for i, (due, _, _) in enumerate(reqs):
        r = done[i] if i < len(done) else None
        ok = (r is not None and r.done_t is not None and r.source != "shed"
              and np.isfinite(r.makespan))
        failed += not ok
        lat.append((r.done_t - (t0 + due)) if ok else float("inf"))
        in_window += ok and r.done_t - t0 <= seconds
    return lat, failed, in_window


def latency_metrics(names, lat) -> Dict[str, float]:
    """``serve_p<q>_ms`` for each such name: the ``q``-th percentile of
    the latencies, in milliseconds."""
    out = {}
    for name in names:
        m = re.fullmatch(r"serve_p(\d+)_ms", name)
        if m:
            out[name] = 1e3 * harness.percentile(lat, int(m.group(1)))
    return out


def check(cfg, caps, w0, picked, taps, temperature, control=None):
    """The sampled requests against the plain reference.  With a
    ``control`` dict, the reference with float8 inputs to its matrix
    products is also read in the program's place, into
    ``control["logp_gap"]``."""
    import jax
    import jax.numpy as jnp
    fl = features.fleet_arrays(common.reference_fleet(cfg["fleet"], caps))
    d = fl["num_devices"]
    pol = dict(cfg["policy"], segment=None)
    fn, fn8 = (jax.jit(partial(ref.logp_entropy, policy=pol, num_devices=d,
                               precision=p, temperature=temperature))
               for p in ("float32", "float8"))
    lp_gap = mk_rel = sel_gap = ctrl_gap = 0.0
    bad = 0
    for req in picked:
        g = req.graph
        n = g.num_nodes
        sampled, lps, row = taps.by_req[req.req_id]
        sampled = np.asarray(sampled)[:, :n]
        lp_prog = np.asarray(lps[row])[:, :n]
        inp = features.policy_inputs(g, fl)
        inp = features.pad_policy_inputs(inp, int(
            np.asarray(lps).shape[-1]))
        pad = np.zeros((sampled.shape[0], inp["op"].shape[0]), np.int32)
        pad[:, :n] = sampled
        with jax.default_matmul_precision("highest"):
            lp_ref, _ = fn(w0, {k: jnp.asarray(v) for k, v in inp.items()},
                           jnp.asarray(pad))
        lp_ref = np.asarray(lp_ref)[:, :n]
        lp_gap = max(lp_gap, float(np.max(np.abs(lp_ref - lp_prog))))
        if control is not None:
            with jax.default_matmul_precision("highest"):
                lp8, _ = fn8(w0, {k: jnp.asarray(v) for k, v in inp.items()},
                             jnp.asarray(pad))
            ctrl_gap = max(ctrl_gap, float(np.max(np.abs(
                np.asarray(lp8)[:, :n] - lp_ref))))
        si = features.sim_inputs(g, fl)
        mk, _, valid = ref_sim.simulate(si, sampled)
        if req.source == "baseline" and not valid.any():
            # no valid sample: the answer is the best baseline placement
            mk_b, _, valid_b = ref_sim.simulate(si, req.placement[None])
            bad += not valid_b[0]
            mk_rel = max(mk_rel, abs(req.makespan - mk_b[0]) / mk_b[0])
            continue
        match = [j for j in range(len(sampled))
                 if np.array_equal(sampled[j], req.placement)]
        if req.source != "zero_shot" or not match or not valid[match[0]]:
            bad += 1
            continue
        j = match[0]
        mk_rel = max(mk_rel, abs(req.makespan - mk[j]) / mk[j])
        sel_gap = max(sel_gap, (mk[j] - mk[valid].min()) / mk[valid].min())
    if control is not None:
        control["logp_gap"] = ctrl_gap
    lim = cfg["correct"]["limits"]
    return [harness.Check("logp_gap", lp_gap, lim["logp_gap"]),
            harness.Check("makespan_rel", mk_rel, lim["makespan_rel"]),
            harness.Check("select_gap", sel_gap, lim["makespan_rel"]),
            harness.Check("unmatched_answers", bad, 0)]


def run(cell: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    from repro.obs.trace import Tracer, set_tracer
    from repro.serve import service as service_mod
    from repro.serve.service import PlacementService, WallClock
    from chipbench import weights

    cfg, mix, seconds = cell["config"], cell["traffic"], cell["seconds"]
    w_seed, t_seed, s_seed, pick_seed = harness.sub_seeds(cell["seed"], 4)
    counter = harness.CompileCounter()
    reqs = traffic.window_requests(mix, seconds)
    graphs = [common.graph({"family": f, "kwargs": kw}) for _, f, kw in reqs]
    warm = [common.graph(w) for w in mix["warmup"]]
    caps = common.mem_caps(cfg["fleet"], None)
    topo = common.topology(cfg["fleet"], caps)
    params = weights.make(w_seed, cfg["policy"])
    w0 = jax.tree_util.tree_map(np.asarray, params)
    tr = common.trainer(common.policy_config(cfg["policy"]),
                        common.ppo_config(cfg["ppo"]), t_seed, params)
    scfg = serve_config(cfg, s_seed)
    warm_svc = PlacementService(tr, scfg, clock=WallClock())
    for g in warm:
        warm_svc.submit(g, topo)
        warm_svc.step(force=True)
    jax.block_until_ready(tr.state.params)
    del warm_svc
    svc = PlacementService(tr, scfg, clock=WallClock())
    taps = Taps(service_mod)
    taps.watch(svc)
    setup_s = time.perf_counter() - cell["t_start"]
    harness.log(f"set-up {setup_s:.3f} s; {len(reqs)} requests, "
                f"{counter.n} backend compiles")

    tracer = Tracer(enabled=bool(cell["trace"]))
    old = set_tracer(tracer)
    compiles0 = counter.n
    traced = trace.Capture() if cell["trace"] else None
    try:
        t0, done, late = window(svc, topo, reqs, graphs, seconds, traced)
    finally:
        set_tracer(old)
        taps.close()
    compiles = counter.n - compiles0
    lat, failed, in_window = summarize(reqs, done, t0, seconds)
    harness.log(f"{len(done)} requests, {failed} failed; generator "
                f"lateness max {max(late):.4f} s mean "
                f"{float(np.mean(late)):.4f} s; {compiles} backend compiles "
                f"in the window; sources "
                f"{dict((k, v) for k, v in svc.counts.items() if v)}")
    peak = harness.peak_bytes(cell["devices"])

    rng = np.random.default_rng(pick_seed)
    answered = [r for r in done if r.done_t is not None and
                r.req_id in taps.by_req]
    k = min(int(cfg["correct"]["requests"]), len(answered))
    largest = max(answered, key=lambda r: r.graph.num_nodes)
    picked = [largest] + [answered[i] for i in rng.choice(
        len(answered), size=k, replace=False) if answered[i] is not largest]
    picked = picked[:k]
    cache_hits = svc.counts["cache"]
    del svc, tr, params
    gc.collect()
    t_ref = time.perf_counter()
    control = {} if cell.get("control") else None
    checks = check(cfg, caps, w0, picked, taps, scfg.temperature, control)
    harness.log(f"reference {time.perf_counter() - t_ref:.3f} s over "
                f"{len(picked)} requests")
    metrics = latency_metrics(cell["metrics"], lat)
    harness.log("latency from the due time: " + ", ".join(
        f"{k} {v:.1f}" for k, v in metrics.items()) +
        f"; {in_window} answered inside the window")
    metrics["setup_s"] = setup_s
    out = {"metrics": metrics, "answered_in_window": in_window,
           "attempted": len(reqs), "failed": failed, "checks": checks,
           "memory_peak_bytes": peak, "control": control}
    if traced is not None:
        red = trace.reduce(traced.raw, cell["layers"], [
            (s.ts, s.ts + s.dur, s.name) for s in tracer.spans],
            traced.clock_start)
        t_lo = traced.clock_start
        t_hi = t_lo + red["window_s"]
        m = int(cfg["serve"]["num_samples"])
        fl_sum = sum(flops.sample(nn, cfg["policy"], m)
                     for c in taps.calls if t_lo <= c["t"] <= t_hi
                     for nn in c["nodes"])
        out["layer_inputs"] = {
            "trace": red, "compiles_in_window": compiles, "flops": fl_sum,
            "peak_flops": cell["peak_flops"],
            "batch_span_s": [s.dur for s in tracer.spans
                             if s.name == "serve.batch" and t_lo <= s.ts
                             and s.ts + s.dur <= t_hi],
            "cache_hits": cache_hits, "requests": len(reqs)}
    return out
