"""Production training driver (GDP policy, or a model-zoo LM on CPU).

GDP mode (default — the paper's training loop):
  PYTHONPATH=src python -m repro.launch.train --iterations 300 \
      --ckpt-dir /tmp/gdp_run --graphs rnnlm:2,gnmt:2,transformer_xl:2

  * checkpoint every --ckpt-every iterations (atomic, async, keep-3)
  * auto-resume from the latest checkpoint in --ckpt-dir
  * SIGTERM/SIGINT triggers a final synchronous save (preemption safety)
  * per-graph running baselines and RNG state survive restarts

LM mode (sanity-scale zoo training on CPU):
  PYTHONPATH=src python -m repro.launch.train --mode lm --arch qwen3-8b \
      --steps 100
  trains the REDUCED config of the arch on the deterministic synthetic
  pipeline; on TPU the same step functions drive the full configs through
  jit with the sharding rules in repro/dist (see dryrun.py).

Scale-out notes (1000+ nodes) are in DESIGN.md §6: gradient compression
hooks live in repro/optim/compress.py; elastic restarts re-shard
checkpoints onto the current mesh.
"""
from __future__ import annotations

import argparse
import signal
import time

import jax
import numpy as np

from repro.obs import jaxprof


def train_gdp(args) -> None:
    from benchmarks import common as C
    from repro.ckpt import CheckpointManager
    from repro.core.ppo import PPOTrainer
    from repro.graphs.synthetic import make_graph

    graphs = [s.strip() for s in args.graphs.split(",") if s.strip()]
    tasks = []
    for spec in graphs:
        g = make_graph(spec, time_steps=args.time_steps) \
            if spec.split(":")[0] in ("rnnlm", "gnmt") else make_graph(spec)
        d = min(int(spec.split(":")[1]) if ":" in spec else 2, 8)
        tasks.append(C.make_task(spec, g, d))
    tuples = [(t.name, t.gb, t.env, t.num_devices) for t in tasks]

    tr = PPOTrainer(C.POLICY, C.PPO, seed=args.seed)
    mgr = CheckpointManager(args.ckpt_dir, keep=3)
    start = 0
    template = {"params": tr.state.params, "opt": tr.state.opt_state,
                "baselines": {}, "counts": {}, "step": 0}
    try:
        restored, meta = mgr.restore_latest(template)
        tr.state.params = restored["params"]
        tr.state.opt_state = restored["opt"]
        tr.state.baselines = dict(restored["baselines"])
        tr.state.baseline_counts = dict(restored["counts"])
        tr.state.step = int(restored["step"])
        start = int(meta.get("iteration", 0))
        print(f"[train] resumed from iteration {start}")
    except FileNotFoundError:
        print("[train] fresh start")

    stop = {"flag": False}

    def _sig(_s, _f):
        stop["flag"] = True
        print("[train] preemption signal — saving and exiting")
    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)

    def snapshot(it):
        mgr.save(it, {"params": tr.state.params, "opt": tr.state.opt_state,
                      "baselines": tr.state.baselines,
                      "counts": tr.state.baseline_counts,
                      "step": tr.state.step},
                 metadata={"iteration": it})

    best = {}
    t0 = time.time()
    for it in range(start, args.iterations):
        for (name, gb, env, nd) in tuples:
            m = tr.iteration(name, gb, env, nd)
            if np.isfinite(m["best_makespan"]):
                best[name] = min(best.get(name, np.inf), m["best_makespan"])
        if it % args.log_every == 0:
            msg = " ".join(f"{k}={v:.4f}" for k, v in best.items())
            print(f"[train] it={it} ({time.time()-t0:.0f}s) {msg}", flush=True)
        if it and it % args.ckpt_every == 0:
            snapshot(it)
        if stop["flag"]:
            break
    mgr.wait()
    snapshot(args.iterations if not stop["flag"] else it)
    mgr.wait()
    print(f"[train] done; best: "
          + " ".join(f"{k}={v:.4f}" for k, v in best.items()))


def train_lm(args) -> None:
    from repro.configs import get_reduced
    from repro.data import TokenPipeline
    from repro.models.model import build_model
    import jax.numpy as jnp

    cfg = get_reduced(args.arch)
    model = build_model(cfg)
    state = model.init_train_state(jax.random.PRNGKey(args.seed))
    step_fn = jax.jit(model.make_train_step())
    pipe = TokenPipeline(vocab=cfg.vocab, batch=args.lm_batch,
                         seq_len=args.lm_seq, seed=args.seed)
    t0 = time.time()
    for s in range(args.steps):
        hb = pipe.global_batch(s)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        state, metrics = step_fn(state, batch)
        if s % 20 == 0 or s == args.steps - 1:
            print(f"[lm:{args.arch}] step={s} loss={float(metrics['loss']):.4f} "
                  f"({time.time()-t0:.0f}s)", flush=True)
    print("[lm] done")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("gdp", "lm"), default="gdp")
    ap.add_argument("--iterations", type=int, default=200)
    ap.add_argument("--graphs", default="rnnlm:2,gnmt:2,transformer_xl:2")
    ap.add_argument("--time-steps", type=int, default=6)
    ap.add_argument("--ckpt-dir", default="/tmp/gdp_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lm-batch", type=int, default=8)
    ap.add_argument("--lm-seq", type=int, default=64)
    args = ap.parse_args()
    jaxprof.enable_compile_cache()
    if args.mode == "gdp":
        train_gdp(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
