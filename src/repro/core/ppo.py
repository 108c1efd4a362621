"""PPO trainer for the GDP policy (paper §3, §4.1).

Reward protocol exactly as the paper: r = −√runtime, −10 for invalid
placements; the *bias* (baseline) is the running average of all previous
trials' rewards for that graph; advantage = r − bias.  The surrogate is the
standard clipped PPO objective with per-node ratios (each node's device
choice is an action sharing the episode advantage) plus an entropy bonus.

Supports GDP-one (single graph), GDP-batch (Eq. 1, mean over a graph set),
fine-tuning from a pre-trained checkpoint, and zero-shot evaluation.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import policy as policy_mod
from repro.core.featurize import GraphBatch
from repro.core.policy import PolicyConfig
from repro.obs import jaxprof
from repro.obs.metrics import RunLog
from repro.obs.trace import get_tracer
from repro.optim import AdamConfig, adam_init, adam_update, clip_by_global_norm
from repro.optim.clip import sanitize


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    """PPO hyperparameters (paper protocol plus beyond-paper variance
    reducers, each individually switchable — see field comments)."""
    lr: float = 1e-3
    clip_eps: float = 0.2
    epochs: int = 3
    num_samples: int = 32         # placements sampled per graph per iteration
    entropy_coef: float = 0.02
    entropy_decay: float = 0.997  # anneal exploration over iterations
    grad_clip: float = 1.0
    adv_norm: bool = True
    # "running_avg": the paper's bias (average of all previous trials).
    # "loo": leave-one-out within the sample batch — a beyond-paper variance
    # reduction recorded separately in EXPERIMENTS.md.
    baseline: str = "running_avg"
    # Per-node counterfactual credit: for every (node, device) pool the
    # rewards of the samples that made that choice; a node's advantage is
    # its chosen cell's pooled mean minus the batch mean.  This collapses
    # the variance of the single-scalar-reward estimator (the paper buys
    # the same effect with hardware-parallel trial farms).  Beyond-paper;
    # benchmarks report both modes.
    per_node_credit: bool = True
    credit_mix: float = 0.5       # blend: per-node + global advantage
    # Canonical device relabeling: makespan is invariant under device
    # permutation, so each sampled placement is relabeled by first
    # appearance along topo order before the update (data augmentation onto
    # the canonical fundamental domain).  Collapses the D! symmetric modes
    # the policy would otherwise have to split probability mass across.
    # Beyond-paper; recorded in EXPERIMENTS.md.
    canonicalize: bool = True


@dataclasses.dataclass
class TrainState:
    """Mutable training state: params, optimizer, per-graph baselines."""
    params: Any
    opt_state: Any
    baselines: Dict[str, float]       # per-graph running-average reward
    baseline_counts: Dict[str, int]
    step: int = 0
    entropy_scale: float = 1.0


def init_state(key, pcfg: PolicyConfig, ocfg: AdamConfig) -> TrainState:
    """Fresh TrainState: initialized policy params + Adam state."""
    params = policy_mod.init(key, pcfg)
    return TrainState(params=params, opt_state=adam_init(params, ocfg),
                      baselines={}, baseline_counts={})


def clone_state(state: TrainState) -> TrainState:
    """Independent copy of a TrainState (superposition fine-tune forks the
    shared pre-trained policy per graph without mutating the original)."""
    copy = jax.tree_util.tree_map(lambda x: x, (state.params, state.opt_state))
    return TrainState(params=copy[0], opt_state=copy[1],
                      baselines=dict(state.baselines),
                      baseline_counts=dict(state.baseline_counts),
                      step=state.step, entropy_scale=state.entropy_scale)


def _loss_fn(params, pcfg: PolicyConfig, gb: GraphBatch, num_devices: int,
             placements, old_logp, adv, clip_eps, entropy_coef):
    new_lp, ent = policy_mod.logp_and_entropy(params, pcfg, gb, num_devices,
                                              placements)
    ratio = jnp.exp(jnp.clip(new_lp - old_logp, -10.0, 10.0))   # [M, N]
    a = adv if adv.ndim == 2 else adv[:, None]                  # [M,N] or [M,1]
    surr = jnp.minimum(ratio * a, jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * a)
    denom = jnp.maximum(gb.node_mask.sum(), 1.0)
    pg = -(surr * gb.node_mask[None, :]).sum(-1) / denom        # [M]
    loss = pg.mean() - entropy_coef * ent
    # PPO health telemetry (masked, per-node actions): clip fraction is
    # how much of the surrogate the clip is actually shaping; approx-KL
    # is the standard E[old - new] drift estimator
    mask = gb.node_mask[None, :]
    m_total = denom * placements.shape[0]
    clip_frac = ((jnp.abs(ratio - 1.0) > clip_eps) * mask).sum() / m_total
    approx_kl = ((old_logp - new_lp) * mask).sum() / m_total
    return loss, {"pg": pg.mean(), "entropy": ent,
                  "clip_frac": clip_frac, "approx_kl": approx_kl}


@partial(jax.jit, static_argnames=("pcfg", "num_devices"))
def _update_grad(params, pcfg: PolicyConfig, gb: GraphBatch,
                 num_devices: int, placements, old_logp, adv, clip_eps,
                 entropy_coef):
    """Loss, telemetry and gradient of the clipped surrogate."""
    return jax.value_and_grad(_loss_fn, has_aux=True)(
        params, pcfg, gb, num_devices, placements, old_logp, adv, clip_eps,
        entropy_coef)


@partial(jax.jit, static_argnames=("ocfg",))
def _update_optim(grads, opt_state, params, ocfg: AdamConfig, grad_clip):
    """Sanitize, clip and Adam: (params, opt_state, pre-clip norm)."""
    grads = sanitize(grads)
    grads, gnorm = clip_by_global_norm(grads, grad_clip)
    params, opt_state = adam_update(grads, opt_state, params, ocfg)
    return params, opt_state, gnorm


def _update_fn(params, opt_state, pcfg: PolicyConfig, ocfg: AdamConfig,
               gb: GraphBatch, num_devices: int, placements, old_logp, adv,
               clip_eps, entropy_coef, grad_clip):
    """One PPO step as two compiled programs: the gradient of the clipped
    surrogate, then sanitize, clip and Adam.  The spans ``ppo.update.grad``
    and ``ppo.update.optim`` time the dispatch of one program each."""
    tracer = get_tracer()
    with tracer.span("ppo.update.grad", cat="ppo"):
        (loss, aux), grads = _update_grad(
            params, pcfg, gb, num_devices, placements, old_logp, adv,
            clip_eps, entropy_coef)
    with tracer.span("ppo.update.optim", cat="ppo"):
        params, opt_state, gnorm = _update_optim(grads, opt_state, params,
                                                 ocfg, grad_clip)
    aux = dict(aux, loss=loss, gnorm=gnorm)
    return params, opt_state, aux


@partial(jax.jit, static_argnames=("pcfg", "num_devices", "num_samples"))
def _sample(params, pcfg: PolicyConfig, gb: GraphBatch, num_devices: int,
            key, num_samples: int):
    return policy_mod.sample(params, pcfg, gb, num_devices, key, num_samples)


@partial(jax.jit, static_argnames=("pcfg", "num_devices"))
def _logp(params, pcfg: PolicyConfig, gb: GraphBatch, num_devices: int,
          placements):
    return policy_mod.logp_and_entropy(params, pcfg, gb, num_devices,
                                       placements)


# "one program per (bucket, D) config" — iterations 2..N must reuse the
# programs traced in iteration 1; tests pin these registrations' deltas
jaxprof.register("ppo.update", _update_grad)
jaxprof.register("ppo.update.optim", _update_optim)
jaxprof.register("ppo.sample", _sample)
jaxprof.register("ppo.logp", _logp)


# Every config re-scores and updates through the jitted programs: the
# segmented TF pass is a ``lax.scan`` over segments, so its program does
# not grow with the graph.  Segmented sampling stays eager: the AR decode
# is a Python loop over per-segment compiled scans, and an outer jit would
# trace that loop into one graph-sized XLA program.
def _sample_any(params, pcfg, gb, num_devices, key, num_samples):
    if pcfg.segment is None:
        return _sample(params, pcfg, gb, num_devices, key, num_samples)
    return policy_mod.sample(params, pcfg, gb, num_devices, key, num_samples)


def _logp_any(params, pcfg, gb, num_devices, placements):
    return _logp(params, pcfg, gb, num_devices, placements)


def _update_any(params, opt_state, pcfg, ocfg, gb, num_devices, placements,
                old_logp, adv, clip_eps, entropy_coef, grad_clip):
    # through the module global, so a patched ``_update_fn`` is the one run
    return _update_fn(params, opt_state, pcfg, ocfg, gb, num_devices,
                      placements, old_logp, adv, clip_eps, entropy_coef,
                      grad_clip)


def canonical_relabel(placements: np.ndarray, num_nodes: int) -> np.ndarray:
    """Relabel each row's devices by first appearance along topo order
    (vectorized: paper-scale rows make a per-element Python loop the
    bottleneck of a PPO iteration)."""
    out = placements.copy()
    m, _ = placements.shape
    dmax = int(placements.max()) + 1 if placements.size else 1
    for i in range(m):
        row = placements[i, :num_nodes]
        first = np.full(dmax, num_nodes, np.int64)
        np.minimum.at(first, row, np.arange(row.size))
        rank = np.empty(dmax, placements.dtype)
        rank[np.argsort(first, kind="stable")] = np.arange(
            dmax, dtype=placements.dtype)
        out[i, :num_nodes] = rank[row]
    return out


def _per_node_advantage(placements: np.ndarray, rewards: np.ndarray,
                        num_devices: int, global_adv: np.ndarray,
                        mix: float) -> np.ndarray:
    """Counterfactual per-(node,device) pooled advantage, [M, N]."""
    m, n = placements.shape
    cnt = np.zeros((num_devices, n))
    srw = np.zeros((num_devices, n))
    for d in range(num_devices):
        sel = placements == d
        cnt[d] = sel.sum(0)
        srw[d] = (sel * rewards[:, None]).sum(0)
    cell = np.where(cnt > 0, srw / np.maximum(cnt, 1), 0.0)
    cell = cell - rewards.mean()
    cell = np.where(cnt > 0, cell, 0.0)
    # gather cell[placements[m, v], v] -> [M, N]
    per_node = cell[placements, np.arange(n)[None, :]]
    scale = per_node.std() + 1e-8
    gscale = max(global_adv.std(), 1e-3)
    return (mix * per_node / scale * gscale +
            (1 - mix) * global_adv[:, None]).astype(np.float32)


class PPOTrainer:
    """Drives PPO over one or many (GraphBatch, Env) tasks."""

    def __init__(self, pcfg: PolicyConfig, ppo: PPOConfig, seed: int = 0,
                 state: Optional[TrainState] = None):
        self.pcfg = pcfg
        self.ppo = ppo
        self.ocfg = AdamConfig(lr=ppo.lr)
        self.key = jax.random.PRNGKey(seed)
        self.state = state or init_state(jax.random.PRNGKey(seed + 1),
                                         pcfg, self.ocfg)
        self.history: List[Dict[str, float]] = []
        # run-scoped JSONL emitter; benchmarks attach one so every
        # train/finetune iteration streams its record next to BENCH rows
        self.run_log: Optional[RunLog] = None

    def _emit(self, record: Dict[str, Any]) -> None:
        if self.run_log is not None:
            self.run_log.emit(record)

    # ------------------------------------------------------------------
    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def _baseline(self, name: str) -> float:
        return self.state.baselines.get(name, 0.0)

    def _update_baseline(self, name: str, rewards: np.ndarray):
        # running average of ALL previous trials (paper §4.1)
        c = self.state.baseline_counts.get(name, 0)
        b = self.state.baselines.get(name, 0.0)
        total = b * c + float(rewards.sum())
        c_new = c + rewards.size
        self.state.baselines[name] = total / c_new
        self.state.baseline_counts[name] = c_new

    # ------------------------------------------------------------------
    def iteration(self, name: str, gb: GraphBatch, env,
                  num_devices: int) -> Dict[str, float]:
        """One PPO iteration on a single graph task.

        The returned record carries the training-health telemetry
        (clip fraction, approx-KL, feasible-sample rate, wall time, jit
        retrace count and backend compiles for this iteration) alongside
        the reward numbers; ``train``/``finetune`` stream these records to
        an attached :class:`~repro.obs.metrics.RunLog`.
        """
        tracer = get_tracer()
        mon = jaxprof.RetraceMonitor()
        compiles0 = jaxprof.backend_compiles()
        t_start = time.perf_counter()
        with tracer.span("ppo.sample", cat="ppo", graph=name):
            placements, old_logp = _sample_any(self.state.params, self.pcfg,
                                               gb, num_devices,
                                               self._next_key(),
                                               self.ppo.num_samples)
            if self.ppo.canonicalize:
                host = np.asarray(placements)
                with tracer.span("ppo.relabel", cat="ppo"):
                    host = canonical_relabel(host, gb.num_nodes)
                placements = jnp.asarray(host)
                with tracer.span("ppo.logp", cat="ppo"):
                    old_logp, _ = _logp_any(self.state.params, self.pcfg, gb,
                                            num_devices, placements)
        with tracer.span("ppo.simulate", cat="ppo", graph=name):
            makespans, rewards, valid = env.rewards(placements)
            rewards_np = np.asarray(rewards)
        if self.ppo.baseline == "loo" and rewards_np.size > 1:
            m = rewards_np.size
            adv = (rewards_np - rewards_np.mean()) * m / (m - 1)
        else:
            bias = self._baseline(name) if self.state.baseline_counts.get(name, 0) \
                else float(rewards_np.mean())
            adv = rewards_np - bias
        if self.ppo.adv_norm and adv.std() > 1e-6:
            adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        if self.ppo.per_node_credit:
            adv = _per_node_advantage(np.asarray(placements), rewards_np,
                                      num_devices, adv, self.ppo.credit_mix)
        self._update_baseline(name, rewards_np)

        ent_coef = self.ppo.entropy_coef * self.state.entropy_scale
        aux = {}
        with tracer.span("ppo.update", cat="ppo", graph=name,
                         epochs=self.ppo.epochs):
            for _ in range(self.ppo.epochs):
                p, o, aux = _update_any(self.state.params,
                                        self.state.opt_state,
                                        self.pcfg, self.ocfg, gb,
                                        num_devices, placements, old_logp,
                                        adv,
                                        self.ppo.clip_eps, ent_coef,
                                        self.ppo.grad_clip)
                self.state.params, self.state.opt_state = p, o
        self.state.step += 1
        self.state.entropy_scale *= self.ppo.entropy_decay
        mk_valid = np.where(np.asarray(valid), np.asarray(makespans), np.inf)
        best = float(mk_valid.min())
        best_pl = (np.asarray(placements[int(mk_valid.argmin())], np.int32)
                   if np.isfinite(best) else None)
        return {"graph": name, "reward_mean": float(rewards_np.mean()),
                "best_makespan": best, "best_placement": best_pl,
                "valid_frac": float(np.asarray(valid).mean()),
                "loss": float(aux.get("loss", 0.0)),
                "entropy": float(aux.get("entropy", 0.0)),
                "clip_frac": float(aux.get("clip_frac", 0.0)),
                "approx_kl": float(aux.get("approx_kl", 0.0)),
                "iter_s": time.perf_counter() - t_start,
                "retraces": mon.total_delta(),
                "compiles": jaxprof.backend_compiles() - compiles0}

    # ------------------------------------------------------------------
    def train(self, tasks: List[Tuple[str, GraphBatch, Any, int]],
              iterations: int, log_every: int = 10,
              callback: Optional[Callable[[int, Dict], None]] = None
              ) -> Dict[str, float]:
        """GDP-one (len==1) or GDP-batch (len>1, Eq. 1 round-robin)."""
        best: Dict[str, float] = {}
        t0 = time.time()
        for it in range(iterations):
            for (name, gb, env, nd) in tasks:
                m = self.iteration(name, gb, env, nd)
                if np.isfinite(m["best_makespan"]):
                    best[name] = min(best.get(name, np.inf), m["best_makespan"])
                m["iter"] = it
                m["elapsed_s"] = time.time() - t0
                rec = {k: v for k, v in m.items() if k != "best_placement"}
                rec["best_so_far"] = best.get(name, float("inf"))
                self.history.append(rec)
                self._emit(dict(rec, phase="train"))
                if callback:
                    callback(it, m)
                # iteration 0 always logs (first signal a run is healthy),
                # then every log_every-th; the stdout line renders the
                # same record that streams to the JSONL
                if log_every and (it == 0 or it % log_every == 0):
                    print(f"[ppo] it={it:4d} {name:>18s} "
                          f"r̄={rec['reward_mean']:+.3f} "
                          f"best={rec['best_so_far']:.4f}s "
                          f"valid={rec['valid_frac']:.2f} "
                          f"kl={rec['approx_kl']:.4f} "
                          f"clip={rec['clip_frac']:.2f}")
        return best

    # ------------------------------------------------------------------
    def finetune(self, name: str, gb: GraphBatch, env, num_devices: int,
                 iterations: int, target: Optional[float] = None,
                 ) -> Dict[str, Any]:
        """Reusable fine-tune hook (paper §3.3 superposition fine-tuning).

        Runs up to ``iterations`` PPO iterations on one graph, tracking the
        best *valid placement* seen across all sampled trials — the
        artifact a serving cache wants back, not just the scalar makespan.
        Early-stops once ``target`` (e.g. the best-baseline makespan) is
        beaten.  Callers that must not mutate a shared policy fork the
        trainer first via ``clone_state`` /
        ``PPOTrainer(pcfg, ppo, state=clone_state(base.state))``.
        """
        best_mk, best_pl, it_run = np.inf, None, 0
        for it_run in range(1, iterations + 1):
            m = self.iteration(name, gb, env, num_devices)
            if m["best_makespan"] < best_mk:
                best_mk = m["best_makespan"]
                best_pl = m["best_placement"]
            self._emit(dict({k: v for k, v in m.items()
                             if k != "best_placement"},
                            phase="finetune", iter=it_run,
                            best_so_far=float(best_mk)))
            if target is not None and best_mk <= target:
                break
        return {"best_makespan": float(best_mk), "best_placement": best_pl,
                "iterations": it_run}

    # ------------------------------------------------------------------
    def eval_greedy(self, gb: GraphBatch, env, num_devices: int
                    ) -> Tuple[float, bool]:
        """(makespan, valid) of the greedy (argmax) decode."""
        pl = policy_mod.greedy(self.state.params, self.pcfg, gb, num_devices)
        mk, r, valid = env.rewards(pl[None])
        return float(mk[0]), bool(valid[0])

    def best_of_samples(self, gb: GraphBatch, env, num_devices: int,
                        m: int = 16) -> float:
        """Best valid makespan over ``m`` sampled placements (zero-shot
        evaluation: no weight updates)."""
        pl, _ = _sample_any(self.state.params, self.pcfg, gb, num_devices,
                            self._next_key(), m)
        mk, _, valid = env.rewards(pl)
        mk = np.where(np.asarray(valid), np.asarray(mk), np.inf)
        return float(mk.min())
