"""Placement-as-a-service: cached, batched, async placement serving.

The serving ladder, cheapest rung first (GDP's generalization story turned
into a system):

1. **Cache hit** — the request's (graph, topology) fingerprint is known:
   return the stored placement remapped through the request graph's
   canonical order.  O(lookup).
2. **Disk hit** — when a persistent store (``serve.persist``) is attached,
   a memory miss probes the on-disk view before paying inference; fresh
   (current-policy) entries are re-admitted to the cache and served.
3. **Zero-shot batch inference** — remaining misses are micro-batched by
   compiled shape and served by ONE jitted policy call per flush
   (``policy.sample_batch``); the best *valid* sampled placement (falling
   back to the best feasible baseline if none is valid) is returned and
   inserted into the cache.
4. **Fine-tune escalation** — if the zero-shot makespan trails the best
   baseline by more than ``escalate_margin``, the graph is queued for a
   background superposition fine-tune (a PPO fork of the shared policy via
   ``ppo.clone_state``; the base policy is never mutated).  Improved
   placements are *published* back into the cache, so repeat traffic picks
   them up — the cache warms toward fine-tuned quality.

Every publish is mirrored to the persistent store (when attached) with
versioned provenance (policy hash, fine-tune step, topology digest), so a
restarted service warm-starts from disk and a policy-version bump
invalidates stale entries instead of serving them.

The whole ladder runs under one simulator mode: with
``ServeConfig.sender_contention`` on, the zero-shot sample selection, the
baseline fallbacks, and fine-tune escalations are all judged by the
contention-aware scheduler, the topology digest in every cache/store key
carries the mode, and the persistent store invalidates cross-mode records
at load — flipping the mode behaves exactly like a policy bump
(re-inference, ``stale_served == 0``).

Determinism: with ``simulated=True`` the service charges a deterministic
service-time model (``ServiceCosts``) against a :class:`SimulatedClock`
instead of reading wall time, so throughput / latency / hit-rate are exact
functions of the request trace and unit-testable.  Batches flush when full
at submit time, when their oldest request has out-waited ``max_wait_s`` at
the next ``step()``, or early when a request's deadline (``deadline_s``)
leaves only one batch's worth of slack.

One ``PlacementService`` is one worker; ``serve.cluster`` shards a fleet
of them behind a consistent-hash router with admission control.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from functools import partial
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core import baselines as B
from repro.core import policy as policy_mod
from repro.core.featurize import bucket_size, featurize, jumbo_bucket
from repro.core.policy import PolicyConfig
from repro.core.ppo import PPOTrainer, clone_state
from repro.obs import jaxprof
from repro.obs.metrics import CounterDict, Histogram, MetricsRegistry
from repro.obs.trace import get_tracer
from repro.sim.device import Topology
from repro.sim.scheduler import Env, SimConfig, prepare_sim_graph
from repro.serve import fingerprint as FP
from repro.serve.batcher import MicroBatcher
from repro.serve.cache import CacheEntry, PlacementCache
from repro.serve.persist import PersistentStore
from repro.serve.persist import policy_hash as _policy_hash


# ------------------------------------------------------------------ clocks
class WallClock:
    """Real time; latency is whatever the hardware delivers."""
    simulated = False

    def now(self) -> float:
        """Current wall time in seconds (monotonic)."""
        return time.perf_counter()

    def advance(self, dt: float) -> None:
        """No-op: wall time advances itself."""
        pass


class SimulatedClock:
    """Deterministic logical time the driver and service advance explicitly.

    In a multi-host cluster each worker owns one of these — a worker's
    clock running ahead of arrivals *is* its queue backlog, which the
    router's admission control reads as load."""
    simulated = True

    def __init__(self, t0: float = 0.0):
        self._t = float(t0)

    def now(self) -> float:
        """Current logical time in seconds."""
        return self._t

    def advance(self, dt: float) -> None:
        """Charge ``dt`` seconds of work (must be non-negative)."""
        assert dt >= 0.0, dt
        self._t += dt

    def advance_to(self, t: float) -> None:
        """Fast-forward to ``t`` if it is in the future (never rewinds)."""
        self._t = max(self._t, float(t))


@dataclasses.dataclass(frozen=True)
class ServiceCosts:
    """Deterministic service-time model charged in simulated-clock mode."""
    lookup_s: float = 1e-4            # cache probe + canonical remap
    store_lookup_s: float = 5e-4      # on-disk view probe + re-admit
    batch_base_s: float = 0.05        # one jitted policy call
    batch_per_graph_s: float = 0.01   # marginal slot cost inside the call
    single_per_graph_s: float = 0.04  # unbatched call, for rate modeling
    finetune_iter_s: float = 0.5      # one PPO iteration
    jumbo_per_knode_s: float = 0.01   # segmented decode, per 1k nodes
    # worker-side typed-rejection cost; mirrors AdmissionConfig.shed_s
    # (the router-side knob) — keep the two in sync when tuning either
    shed_s: float = 2e-4              # degraded baseline fast path


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Knobs for one serving worker (cache, batching, escalation)."""
    cache_capacity: int = 512
    cache_policy: str = "lru"          # "lru" | "lfu"
    max_batch: int = 8
    max_wait_s: float = 0.05
    deadline_s: float = math.inf       # per-request deadline (early flush)
    num_samples: int = 4               # sampled placements per request
    temperature: float = 0.25          # near-greedy serving decode
    escalate_margin: float = 0.10      # fine-tune if zs > (1+margin)*baseline
    finetune_iters: int = 8
    finetune_per_step: int = 1         # graphs fine-tuned per step()
    max_deg: int = 8
    seed: int = 0
    simulated: bool = False
    # Simulator semantics this worker serves under (SimConfig modes):
    # with any mode on, every env, baseline and fine-tune is judged by
    # the mode-aware scheduler and every key's topology digest carries
    # the full mode set (failure modes are provenance).
    sender_contention: bool = False
    receiver_contention: bool = False
    jittered_bandwidth: bool = False
    jitter_amp: float = 0.25
    jitter_seed: int = 0
    # Jumbo bucket (paper-scale admissions): graphs above the policy's
    # ``scale.jumbo_threshold`` nodes skip the micro-batcher — they are
    # padded to the next multiple of ``scale.jumbo_pad_multiple``
    # (featurize.jumbo_bucket; far tighter than the power-of-two ladder
    # at 50k nodes) and served one at a time through the segmented
    # decode when the policy has one (``scale.segment``).  Graphs above
    # ``max_graph_nodes`` — or topologies wider than the policy head —
    # are REJECTED: a typed shed to the degraded baseline fast path
    # (``Request.rejection``, ``counts["shed_rejected"]``) instead of an
    # assert crashing the worker.
    max_graph_nodes: int = 1 << 17
    costs: ServiceCosts = dataclasses.field(default_factory=ServiceCosts)

    @property
    def sim(self) -> SimConfig:
        """Evaluation :class:`SimConfig` for this worker (shaped off)."""
        return SimConfig(sender_contention=self.sender_contention,
                         receiver_contention=self.receiver_contention,
                         jittered_bandwidth=self.jittered_bandwidth,
                         jitter_amp=self.jitter_amp,
                         jitter_seed=self.jitter_seed)

    @property
    def mode_bits(self) -> int:
        """Packed communication modes (store invalidation key)."""
        return self.sim.mode_bits


@dataclasses.dataclass(frozen=True)
class Rejection:
    """Typed reason an oversized request was shed to the baseline path."""
    reason: str                # "graph_too_large" | "too_many_devices"
    limit: int
    requested: int


@dataclasses.dataclass
class Request:
    """One placement request and, once resolved, its response."""
    req_id: int
    graph: Any
    topo: Topology
    arrival_t: float
    key: Tuple[str, str]
    order: np.ndarray                      # canonical node order
    done_t: Optional[float] = None
    placement: Optional[np.ndarray] = None  # graph node order
    makespan: float = float("inf")
    source: str = "pending"    # cache | disk | zero_shot | baseline | shed
    entry_source: str = ""     # provenance of the cache line that served it
    rejection: Optional[Rejection] = None   # set on typed oversize sheds
    # batched zero-shot path only: added to the batcher, its flush began,
    # its placements reached the host (``phases`` splits the latency)
    queued_t: Optional[float] = None
    flushed_t: Optional[float] = None
    decoded_t: Optional[float] = None

    @property
    def latency(self) -> float:
        """Response time (done - arrival); requires a resolved request."""
        assert self.done_t is not None, "request not resolved yet"
        return self.done_t - self.arrival_t

    def phases(self) -> Dict[str, float]:
        """A batched zero-shot answer's latency by phase, which sum to
        :attr:`latency`: ``prepare`` (fingerprint, lookup, context),
        ``batch_wait`` (queued until its flush began), ``policy`` (the
        batched decode up to the host copy of its placements), ``select``
        (simulate-select, after earlier members of the batch); {} for
        every other answer."""
        if self.decoded_t is None or self.done_t is None:
            return {}
        return {"prepare": self.queued_t - self.arrival_t,
                "batch_wait": self.flushed_t - self.queued_t,
                "policy": self.decoded_t - self.flushed_t,
                "select": self.done_t - self.decoded_t}


@dataclasses.dataclass
class _GraphCtx:
    """Per-(graph_fp, topo_fp) working state, built on first miss.

    ``order`` is the canonical node order of the *specific relabeling* that
    populated ``gb`` — fine-tuned placements (produced in that graph's node
    order) are re-indexed through it before entering the cache, so later
    relabelings of the same graph decode them correctly.
    """
    gb: Any                    # featurized GraphBatch (unpadded)
    env_true: Env              # paper reward (evaluation / serving)
    env_shaped: Env            # shaped reward (fine-tune)
    num_devices: int
    baseline_best: float
    baseline_pl: Optional[np.ndarray]
    order: np.ndarray
    escalated: bool = False


@partial(jax.jit, static_argnames=("pcfg", "num_devices", "num_samples"))
def _sample_batch_jit(params, pcfg: PolicyConfig, sgb, num_devices: int,
                      key, num_samples: int, temperature):
    return policy_mod.sample_batch(params, pcfg, sgb, num_devices, key,
                                   num_samples, temperature)


# the "compiles once per bucket" serving invariant is asserted off this
# registration (tests pin its cache-size delta across warm replays)
jaxprof.register("serve.sample_batch", _sample_batch_jit)

# the serving ladder's historical stats() key set; CounterDict presets it
# so snapshots expose every rung at 0 from the first request
_LADDER_KEYS = ("cache", "disk", "zero_shot", "baseline", "finetunes",
                "finetune_published", "forward_adopted", "stale_served",
                "shed", "shed_rejected", "jumbo")


def latency_summary(latencies, prefix: str = "latency") -> Dict[str, float]:
    """p50/p99/mean of ``latencies`` through the shared Histogram.

    One implementation behind every latency percentile the repo reports
    (worker stats, cluster stats, benchmarks); retained-sample mode makes
    the numbers bit-for-bit equal to the per-call ``np.percentile`` math
    it replaced.  Empty input returns {} (legacy stats() omitted the keys).
    """
    h = Histogram(prefix)
    for v in latencies:
        h.observe(float(v))
    if not h.count():
        return {}
    return {f"{prefix}_p50_s": h.percentile(50),
            f"{prefix}_p99_s": h.percentile(99),
            f"{prefix}_mean_s": h.mean()}


class PlacementService:
    """Synchronous-submit / async-worker placement server.

    ``trainer`` carries the shared (ideally pre-trained) GDP policy used
    for zero-shot inference; fine-tune escalations fork it per graph and
    publish only placements, never parameters.

    Args:
        trainer: PPO trainer holding the zero-shot policy parameters.
        config: serving knobs (:class:`ServeConfig`).
        clock: explicit clock; defaults to a fresh simulated/wall clock
            per ``config.simulated``.
        store: optional :class:`~repro.serve.persist.PersistentStore` —
            the cache warm-starts from its fresh entries, every publish is
            mirrored to it, and memory misses probe it before inference.
        preload: optional key predicate limiting which store entries are
            re-admitted at startup (a cluster passes its shard router so
            each worker only warms its own shard).
    """

    def __init__(self, trainer: PPOTrainer, config: ServeConfig = ServeConfig(),
                 clock=None, store: Optional[PersistentStore] = None,
                 preload: Optional[Callable[[Tuple[str, str]], bool]] = None):
        self.trainer = trainer
        self.pcfg = trainer.pcfg
        self.cfg = config
        self.clock = clock or (SimulatedClock() if config.simulated
                               else WallClock())
        self.store = store
        if store is not None:
            # a store replaying records under different simulator modes
            # would warm the cache with cross-mode placements
            assert store.mode_bits == config.mode_bits, (
                store.mode_bits, config.mode_bits)
        self.policy_hash = (store.policy_hash if store is not None
                            else _policy_hash(trainer.state.params))
        self.cache = PlacementCache(config.cache_capacity, config.cache_policy)
        self.batcher = MicroBatcher(
            config.max_batch, config.max_wait_s, config.max_deg,
            flush_slack_s=(config.costs.batch_base_s +
                           config.max_batch * config.costs.batch_per_graph_s))
        self._ctx: Dict[Tuple[str, str], _GraphCtx] = {}
        # in-flight coalescing: requests for a key already queued for
        # inference wait on that flush instead of re-entering the batcher
        # (classic cache-stampede protection; one model call per key).
        self._inflight: Dict[Tuple[str, str], List[Request]] = {}
        self._ft_queue: Deque[Tuple[Tuple[str, str], str]] = deque()
        self._topo_fp = FP.TopologyFingerprinter(
            **config.sim.comm_mode_kwargs())
        self._key = jax.random.PRNGKey(config.seed)
        self._next_id = 0
        self.completed: List[Request] = []
        # per-worker metrics registry; the historical ``counts`` dict API
        # survives as a CounterDict view over one labeled counter, so the
        # stats() schema (and every `svc.counts[...]` call site) is
        # unchanged while the values ship in snapshots/JSONL/Prometheus
        self.metrics = MetricsRegistry()
        self.counts = CounterDict(
            self.metrics.counter("serve_events_total",
                                 "serving-ladder event counts", ("event",)),
            initial=_LADDER_KEYS)
        self._lat_hist = self.metrics.histogram(
            "serve_latency_seconds",
            "request latency observed at resolve time", ("source",))
        self._phase_hist = self.metrics.histogram(
            "serve_phase_seconds",
            "batched zero-shot latency by phase (Request.phases)",
            ("phase",))
        self.tid = 0   # trace lane; the cluster assigns worker indices
        if self.store is not None:
            for key, se in self.store.items():
                if preload is None or preload(key):
                    self.cache.put(key, se.to_cache_entry())

    # ---------------------------------------------------------------- rng
    def _split(self):
        self._key, k = jax.random.split(self._key)
        return k

    # ------------------------------------------------------------- submit
    def submit(self, g, topo: Topology, arrival_t: Optional[float] = None,
               fp_order: Optional[Tuple[str, np.ndarray]] = None,
               topo_fp: Optional[str] = None) -> Request:
        """Register one request; resolves immediately on a cache/disk hit
        or a full micro-batch, otherwise parks it with the batcher.

        Args:
            g: the dataflow graph to place.
            topo: target device topology.
            arrival_t: logical arrival time (simulated-clock mode).
            fp_order: precomputed ``(graph_fp, canonical_order)`` — the
                cluster router fingerprints once for shard routing and
                passes it down so the WL refinement is not recomputed.
            topo_fp: precomputed topology fingerprint (same reason).

        Returns the (possibly still pending) :class:`Request`.
        """
        if arrival_t is not None and self.clock.simulated:
            self.clock.advance_to(arrival_t)
        with get_tracer().span("serve.submit", cat="serve", clock=self.clock,
                               tid=self.tid):
            return self._submit(g, topo, fp_order, topo_fp)

    def _submit(self, g, topo: Topology,
                fp_order: Optional[Tuple[str, np.ndarray]],
                topo_fp: Optional[str]) -> Request:
        tracer = get_tracer()
        now = self.clock.now()
        with tracer.span("serve.fingerprint", cat="serve", clock=self.clock,
                         tid=self.tid):
            graph_fp, order = fp_order or FP.fingerprint_and_order(g)
            key = (graph_fp, topo_fp or self._topo_fp(topo))
        req = Request(self._next_id, g, topo, now, key, order)
        self._next_id += 1

        # typed admission bounds: an oversized request degrades to the
        # baseline fast path instead of crashing the worker on an assert
        if topo.num_devices > self.pcfg.max_devices:
            return self._shed_rejected(req, "too_many_devices",
                                       self.pcfg.max_devices,
                                       topo.num_devices)
        if g.num_nodes > self.cfg.max_graph_nodes:
            return self._shed_rejected(req, "graph_too_large",
                                       self.cfg.max_graph_nodes,
                                       g.num_nodes)

        with tracer.span("serve.lookup", cat="serve", clock=self.clock,
                         tid=self.tid):
            entry = self.cache.get(key)
            if self.clock.simulated:
                self.clock.advance(self.cfg.costs.lookup_s)
        if entry is not None:
            self._serve_entry(req, entry, "cache")
            return req

        if key in self._inflight:              # coalesce concurrent misses
            # (before the disk rung: an in-flight key cannot be on disk —
            # publishes land in the cache first — so probing would only
            # charge store_lookup_s for a guaranteed miss)
            self._inflight[key].append(req)
            return req

        if self.store is not None:             # disk rung: evicted / warm
            with tracer.span("serve.store_lookup", cat="serve",
                             clock=self.clock, tid=self.tid):
                if self.clock.simulated:
                    self.clock.advance(self.cfg.costs.store_lookup_s)
                se = self.store.lookup(key)
            if se is not None:
                entry = se.to_cache_entry()
                self.cache.put(key, entry)     # re-admit to memory
                self._serve_entry(req, entry, "disk")
                return req
        self._inflight[key] = []
        ctx = self._context(key, g, topo, order)
        if g.num_nodes > self.pcfg.scale.jumbo_threshold:
            # jumbo bucket: segment-padded, served solo — batching would
            # backfill max_batch copies of a 50k-node graph for nothing
            self._serve_jumbo(req, ctx)
            return req
        deadline = (now + self.cfg.deadline_s
                    if math.isfinite(self.cfg.deadline_s) else math.inf)
        req.queued_t = self.clock.now()
        self.batcher.add(
            MicroBatcher.group_key(key[1], ctx.num_devices, g.num_nodes),
            req, ctx.gb, now, deadline=deadline)
        self._flush(self.batcher.ready(now))   # full groups flush instantly
        return req

    def _shed_rejected(self, req: Request, reason: str, limit: int,
                       requested: int) -> Request:
        """Resolve an out-of-bounds request with the degraded baseline
        placement (feasible-by-construction, makespan unverified/NaN) and
        a typed :class:`Rejection`, counting it in ``shed_rejected``."""
        from repro.serve.admission import degraded_placement
        if self.clock.simulated:
            self.clock.advance(self.cfg.costs.shed_s)
        req.rejection = Rejection(reason, limit, requested)
        req.placement = degraded_placement(req.graph, req.topo)
        req.makespan = float("nan")
        req.done_t = self.clock.now()
        req.source = req.entry_source = "shed"
        self.counts["shed"] += 1
        self.counts["shed_rejected"] += 1
        self._lat_hist.observe(req.latency, source="shed")
        self.completed.append(req)
        return req

    def _serve_jumbo(self, req: Request, ctx: "_GraphCtx") -> None:
        """Serve one jumbo admission: a single segmented zero-shot decode
        (no micro-batching), then the normal select/publish/escalate path."""
        n = req.graph.num_nodes
        with get_tracer().span("serve.jumbo", cat="serve", clock=self.clock,
                               tid=self.tid, num_nodes=n):
            if self.clock.simulated:
                self.clock.advance(self.cfg.costs.jumbo_per_knode_s *
                                   max(n, 1) / 1000.0)
            sampled, _ = policy_mod.sample(
                self.trainer.state.params, self.pcfg, ctx.gb,
                ctx.num_devices, self._split(), self.cfg.num_samples,
                self.cfg.temperature)
        self.counts["jumbo"] += 1
        self._serve_zero_shot(req, np.asarray(sampled, np.int32))

    def _serve_entry(self, req: Request, entry: CacheEntry,
                     source: str) -> None:
        """Resolve ``req`` from a cache/disk entry, auditing provenance."""
        if entry.policy_hash and entry.policy_hash != self.policy_hash:
            # must be impossible (load-time invalidation); audited so the
            # cluster benchmark can *measure* zero rather than assume it
            self.counts["stale_served"] += 1
        self._resolve(req, FP.from_canonical(entry.placement, req.order),
                      entry.measured_makespan, source,
                      entry_source=entry.source)

    # --------------------------------------------------------------- step
    def step(self, force: bool = False) -> None:
        """One async-worker turn: flush timed-out batches, then spend the
        fine-tune budget.  ``force`` drains regardless of wait deadlines."""
        self._flush(self.batcher.ready(self.clock.now(), force=force))
        for _ in range(self.cfg.finetune_per_step):
            if not self._ft_queue:
                break
            self._finetune_one(*self._ft_queue.popleft())

    def drain(self) -> None:
        """Flush every queue (end of trace / shutdown)."""
        self.step(force=True)
        while self._ft_queue:
            self._finetune_one(*self._ft_queue.popleft())

    # ---------------------------------------------------------- internals
    def _context(self, key, g, topo: Topology,
                 order: np.ndarray) -> _GraphCtx:
        ctx = self._ctx.get(key)
        if ctx is not None:
            return ctx
        with get_tracer().span("serve.context", cat="serve",
                               clock=self.clock, tid=self.tid):
            return self._new_context(key, g, topo, order)

    def _new_context(self, key, g, topo: Topology,
                     order: np.ndarray) -> _GraphCtx:
        tracer = get_tracer()
        # contexts are a warm-start side table (envs, featurized arrays,
        # baselines); bound them like the cache, sparing in-flight keys
        if len(self._ctx) >= 4 * self.cfg.cache_capacity:
            busy = set(self._inflight) | {k for k, _ in self._ft_queue} | \
                {r.key for r in self.batcher.pending_items()}
            for k in list(self._ctx):
                if k not in busy:
                    del self._ctx[k]
                    if len(self._ctx) < 4 * self.cfg.cache_capacity:
                        break
        nd = topo.num_devices
        if nd > self.pcfg.max_devices:   # submit() sheds before reaching
            raise ValueError(            # here; typed guard, not an assert
                f"topology has {nd} devices, policy head caps at "
                f"{self.pcfg.max_devices}")
        # Bucket-pad EVERYTHING — featurizer, simulator, baselines — so the
        # whole serving path (policy call, sample selection, fine-tune PPO
        # programs) compiles once per (bucket, D) instead of once per
        # distinct graph size; padded nodes are masked throughout.  Jumbo
        # graphs pad to the segment-aligned jumbo bucket instead of the
        # power-of-two ladder (tighter, and divisible by the decoder's
        # segment when one is configured).
        sc = self.pcfg.scale
        if g.num_nodes > sc.jumbo_threshold:
            mult = sc.jumbo_pad_multiple
            if sc.segment:
                mult = max(mult // sc.segment, 1) * sc.segment
            pad_n = jumbo_bucket(g.num_nodes, mult)
        else:
            pad_n = bucket_size(g.num_nodes)
        seg = sc.segment if sc.segment and pad_n % sc.segment == 0 else None
        span = partial(tracer.span, cat="serve", clock=self.clock,
                       tid=self.tid)
        with span("serve.sim_graph"):
            sg = prepare_sim_graph(g, topo, max_deg=16, pad_to=pad_n,
                                   pad_k=16)
            env_true = Env.from_config(sg, topo, self.cfg.sim, segment=seg)
            env_shaped = Env.from_config(
                sg, topo,
                dataclasses.replace(self.cfg.sim, shaped_reward=True),
                segment=seg)
        with span("serve.featurize"):
            gb = featurize(g, max_deg=self.cfg.max_deg, pad_to=pad_n,
                           topo=topo)
        base_best, base_pl = np.inf, None
        with span("serve.baselines"):
            for fn in (B.human_expert, B.round_robin):
                pl = fn(g, topo)
                pl_pad = np.zeros(pad_n, np.int32)
                pl_pad[:g.num_nodes] = pl
                mk, _, ok = env_true.rewards(pl_pad[None])
                if bool(ok[0]) and float(mk[0]) < base_best:
                    base_best, base_pl = float(mk[0]), pl.astype(np.int32)
        ctx = _GraphCtx(gb, env_true, env_shaped, nd, base_best, base_pl,
                        order)
        self._ctx[key] = ctx
        return ctx

    def _resolve(self, req: Request, placement: np.ndarray, makespan: float,
                 source: str, entry_source: str = "") -> None:
        req.done_t = self.clock.now()
        req.placement = np.asarray(placement, np.int32)
        req.makespan = float(makespan)
        req.source = source
        req.entry_source = entry_source or source
        self.counts[source] += 1
        self._lat_hist.observe(req.latency, source=source)
        for phase, dt in req.phases().items():
            self._phase_hist.observe(dt, phase=phase)
        self.completed.append(req)

    def _flush(self, flushes) -> None:
        for fl in flushes:
            with get_tracer().span("serve.batch", cat="serve",
                                   clock=self.clock, tid=self.tid,
                                   real=fl.real):
                flushed_t = self.clock.now()
                if self.clock.simulated:
                    self.clock.advance(
                        self.cfg.costs.batch_base_s +
                        self.cfg.costs.batch_per_graph_s * fl.real)
                # a segmented policy manages its own per-segment compiled
                # programs — wrapping the Python segment loop in the outer
                # jit would trace it into one graph-sized program
                sample_fn = (policy_mod.sample_batch
                             if self.pcfg.scale.segment is not None
                             else _sample_batch_jit)
                placements, _ = sample_fn(
                    self.trainer.state.params, self.pcfg, fl.sgb, fl.key[1],
                    self._split(), self.cfg.num_samples,
                    self.cfg.temperature)
                placements = np.asarray(placements, np.int32)  # [B, M, Npad]
                decoded_t = self.clock.now()
            for i, req in enumerate(fl.items):
                req.flushed_t, req.decoded_t = flushed_t, decoded_t
                self._serve_zero_shot(req, placements[i])

    def _serve_zero_shot(self, req: Request, sampled: np.ndarray) -> None:
        """Pick the best valid sample, fall back to the best baseline, cache
        the winner, and escalate if it trails the baseline badly."""
        ctx = self._ctx[req.key]
        n = req.graph.num_nodes
        pad_n = ctx.gb.op.shape[0]        # ctx arrays live at bucket width
        with get_tracer().span("serve.zero_shot", cat="serve",
                               clock=self.clock, tid=self.tid):
            mks, _, valid = ctx.env_true.rewards(sampled[:, :pad_n])
            mks = np.where(np.asarray(valid), np.asarray(mks), np.inf)
        best = int(mks.argmin())
        pl, mk, source = sampled[best, :n], float(mks[best]), "zero_shot"
        if not np.isfinite(mk) and ctx.baseline_pl is not None:
            pl, mk, source = ctx.baseline_pl, ctx.baseline_best, "baseline"
        if np.isfinite(mk):
            # publish (not put): an unlucky later sample of the same key
            # must never overwrite a better stored placement
            self._publish(req.key, FP.to_canonical(pl, req.order), mk,
                          source=source)
        self._resolve(req, pl, mk, source)
        for waiter in self._inflight.pop(req.key, []):
            self._resolve(waiter,
                          FP.from_canonical(FP.to_canonical(pl, req.order),
                                            waiter.order),
                          mk, source, entry_source="coalesced")
        trails = mk > (1.0 + self.cfg.escalate_margin) * ctx.baseline_best
        if (not ctx.escalated and (trails or not np.isfinite(mk))
                and self.cfg.finetune_iters > 0):
            ctx.escalated = True
            self._ft_queue.append((req.key, req.graph.name))

    def _finetune_one(self, key: Tuple[str, str], name: str) -> None:
        """Background worker: superposition fine-tune one graph from the
        shared base policy; publish the placement iff it improves the
        cached one (PlacementCache.publish enforces monotonicity)."""
        ctx = self._ctx[key]
        with get_tracer().span("serve.finetune", cat="serve",
                               clock=self.clock, tid=self.tid,
                               graph=name) as sp:
            fork = PPOTrainer(self.pcfg, self.trainer.ppo,
                              seed=self.cfg.seed + 17,
                              state=clone_state(self.trainer.state))
            res = fork.finetune(name, ctx.gb, ctx.env_shaped,
                                ctx.num_devices, self.cfg.finetune_iters)
            self.counts["finetunes"] += 1
            if self.clock.simulated:
                self.clock.advance(self.cfg.costs.finetune_iter_s *
                                   res["iterations"])
            sp.set(iterations=res["iterations"])
        if res["best_placement"] is None:
            return
        n = ctx.gb.num_nodes
        if self._publish(key,
                         FP.to_canonical(res["best_placement"][:n],
                                         ctx.order),
                         res["best_makespan"], source="finetuned",
                         finetune_step=res["iterations"]):
            self.counts["finetune_published"] += 1

    # ------------------------------------------------------ publish/store
    def _publish(self, key: Tuple[str, str], canon_pl: np.ndarray,
                 mk: float, source: str, finetune_step: int = 0) -> bool:
        """Monotone cache publish, mirrored to the persistent store."""
        with get_tracer().span("serve.publish", cat="serve",
                               clock=self.clock, tid=self.tid,
                               source=source):
            ok = self.cache.publish(key, canon_pl, mk, source=source,
                                    finetune_step=finetune_step,
                                    policy_hash=self.policy_hash)
            if ok and self.store is not None:
                self.store.record(key, self.cache.peek(key),
                                  finetune_step=finetune_step)
                self.store.maybe_compact()
        return ok

    def adopt(self, key: Tuple[str, str], entry: CacheEntry) -> bool:
        """Install an entry forwarded from another shard (monotone; the
        adopted copy is also persisted so it survives restarts here).

        Returns True iff the entry improved/created this shard's line."""
        ok = self._publish(key, entry.placement, entry.measured_makespan,
                           source=entry.source,
                           finetune_step=entry.finetune_step)
        if ok:
            self.counts["forward_adopted"] += 1
        return ok

    def queue_depth(self) -> int:
        """Unresolved work parked at this worker (batcher + coalesced
        waiters + fine-tune backlog) — the router's admission signal."""
        return (len(self.batcher) +
                sum(len(w) for w in self._inflight.values()) +
                len(self._ft_queue))

    def checkpoint(self) -> None:
        """Snapshot every live cache entry to the persistent store (hit
        counters included, so LRU/LFU state survives a restart)."""
        if self.store is None:
            return
        for key, entry in self.cache.items():
            self.store.record(key, entry,
                              finetune_step=entry.finetune_step)

    def shutdown(self) -> None:
        """Drain all queues, checkpoint the cache, compact and close the
        store.  The service object stays readable (stats, completed)."""
        self.drain()
        if self.store is not None:
            self.checkpoint()
            self.store.compact()
            self.store.close()

    # -------------------------------------------------------------- stats
    def stats(self) -> Dict[str, Any]:
        """Aggregate counters: ladder counts, cache stats, latency
        percentiles over completed requests, queue depths.

        Percentiles are computed over final request latencies at call
        time (not the resolve-time histogram observations) because a
        cluster router back-dates ``arrival_t`` to the true arrival after
        a busy worker resolves; both paths share the
        :func:`latency_summary` implementation.
        """
        out: Dict[str, Any] = dict(self.counts)
        out.update(self.cache.stats.as_dict())
        out["served"] = len(self.completed)
        out["pending"] = len(self.batcher)
        out["ft_queue"] = len(self._ft_queue)
        if self.store is not None:
            out["store"] = self.store.stats.as_dict()
        out.update(latency_summary(r.latency for r in self.completed))
        return out

    def snapshot(self) -> Dict[str, Any]:
        """Point-in-time metrics snapshot (plain JSON-able dict).

        Refreshes the load/cache gauges and the process-wide jit
        retrace gauges first, so the exported view is current.
        """
        g = self.metrics.gauge("serve_queue_depth",
                               "unresolved work parked at this worker")
        g.set(self.queue_depth())
        self.metrics.gauge("serve_cache_entries",
                           "live cache lines").set(len(self.cache))
        jaxprof.export_gauges(self.metrics)
        jaxprof.export_rss_gauge(self.metrics)
        return self.metrics.snapshot()
