"""Model assembly: ArchConfig -> params, train_step, prefill, decode.

Structure (all block groups stacked along a leading ``n_groups`` axis and
driven by ``lax.scan`` — compile time is O(1) in depth, which keeps the
88-layer/123B dry-run lowerable):

    params = {
      "embed":   [V, D]                    (tied LM head by default)
      "lm_head": [V, D]                    (untied head, tie_embeddings=False)
      "groups":  {"slot0": {...}, ...}     leaves [G, ...]
      "enc":     {...}                     (whisper encoder, optional)
      "final_norm": [D]
    }

Memory discipline for the production shapes (DESIGN.md §6): per-group
remat, chunked cross-entropy, optional microbatched gradient accumulation,
bf16/fp32-switchable optimizer state.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import blocks as B
from repro.models import layers as L
from repro.optim import AdamConfig, adam_init, adam_update
from repro.optim.clip import clip_by_global_norm, sanitize


def _dt(name: str):
    return jnp.dtype(name)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    mesh: Optional[object] = None      # jax Mesh: enables SPMD constraints

    # -------------------------------------------------- sharding constraints
    def _dp_axes(self):
        if self.mesh is None:
            return None
        return ("pod", "data") if "pod" in self.mesh.axis_names else ("data",)

    def _c_hidden(self, x: jnp.ndarray) -> jnp.ndarray:
        """Sequence-parallel constraint on the [B, S, D] hidden stream.

        Prefill/train: S over "model" (Megatron-SP — bounds live activation
        memory to S/16 per chip; GSPMD inserts the gather/scatter pairs
        around attention).  Decode (S==1): D over "model".  Batch over the
        FSDP axes when divisible.  No-op without a mesh (CPU smoke paths).
        """
        if self.mesh is None or x.ndim != 3:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        dp = self._dp_axes()
        msize = self.mesh.shape["model"]
        dsize = int(np.prod([self.mesh.shape[a] for a in dp]))
        b, s, d = x.shape
        bax = dp if b % dsize == 0 else None
        if s % msize == 0 and s > 8192:
            # long-context prefill: sequence parallelism (iteration 1)
            spec = P(bax, "model", None)
        elif s > 1 and d % msize == 0:
            # train: keep the hidden TP-aligned (d_model over "model") —
            # seq-sharding here made GSPMD emit per-chunk all-to-alls
            # inside the attention loops (measured: mistral train
            # collective term 225 s).  §Perf iteration 3b.
            spec = P(bax, None, "model")
        elif d % msize == 0:
            # decode (S==1): keep the hidden REPLICATED over the FSDP axes.
            # Batch-sharding it here makes GSPMD all-gather the row-sharded
            # weights every token (measured: arctic decode_32k collective
            # term 1.6 s/token); with a replicated hidden the contraction
            # over the row-sharded dim becomes a tiny [B,1,F] all-reduce
            # instead.  KV caches stay batch-sharded — attention reshards
            # [B,1,D] activations, which is negligible.  §Perf iteration 2.
            spec = P(None, None, "model")
        else:
            spec = P(bax, None, None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    # ------------------------------------------------------------- params
    def init_params(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = _dt(cfg.param_dtype)
        k_emb, k_grp, k_enc = jax.random.split(key, 3)

        def one_group(k):
            ks = jax.random.split(k, cfg.period)
            return {f"slot{j}": B.init_layer(ks[j], cfg, cfg.pattern[j], dtype,
                                             cross_attn=cfg.enc_dec)
                    for j in range(cfg.period)}

        gkeys = jax.random.split(k_grp, cfg.n_groups)
        groups = jax.vmap(one_group)(gkeys)      # leaves get [G, ...]
        params = {
            "embed": jax.random.normal(k_emb, (cfg.vocab, cfg.d_model),
                                       dtype) * 0.02,
            "groups": groups,
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = jax.random.normal(
                jax.random.fold_in(k_emb, 1), (cfg.vocab, cfg.d_model),
                dtype) * cfg.d_model ** -0.5
        if cfg.enc_dec:
            ekeys = jax.random.split(k_enc, cfg.n_enc_layers)
            params["enc"] = {
                "layers": jax.vmap(
                    lambda k: B.init_layer(k, cfg, cfg.pattern[0], dtype)
                )(ekeys),
                "final_norm": jnp.zeros((cfg.d_model,), dtype),
            }
        if cfg.frontend == "vision":
            params["patch_proj"] = jax.random.normal(
                jax.random.fold_in(k_enc, 7), (cfg.d_model, cfg.d_model),
                dtype) * cfg.d_model ** -0.5
        return params

    def param_shapes(self) -> Any:
        return jax.eval_shape(lambda: self.init_params(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------ encoder
    def _encode(self, params, frames: jnp.ndarray) -> jnp.ndarray:
        """Whisper encoder over precomputed frame embeddings [B, S, D]."""
        cfg = self.cfg
        positions = jnp.arange(frames.shape[1])[None, :].repeat(
            frames.shape[0], 0)

        def body(x, lp):
            x, _, _ = B.apply_layer(lp, x, cfg, cfg.pattern[0],
                                    positions=positions, mode="train",
                                    causal=False)
            return x, None

        x, _ = jax.lax.scan(body, frames.astype(_dt(cfg.activ_dtype)),
                            params["enc"]["layers"])
        return L.rmsnorm(params["enc"]["final_norm"], x, cfg.norm_eps)

    # ------------------------------------------------------------ forward
    def _embed_tokens(self, params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        cfg = self.cfg
        x = params["embed"][batch["tokens"]].astype(_dt(cfg.activ_dtype))
        if cfg.frontend == "vision" and "patch_embeds" in batch:
            patches = batch["patch_embeds"].astype(x.dtype) @ params["patch_proj"]
            x = jax.lax.dynamic_update_slice(x, patches, (0, 0, 0))
        return x

    def _positions(self, batch, seq: int, batchsize: int):
        if self.cfg.mrope and "positions" in batch:
            return batch["positions"]                 # [3, B, S]
        return jnp.arange(seq)[None, :].repeat(batchsize, 0)

    def backbone(self, params, x: jnp.ndarray, positions, *,
                 mode: str, caches=None, cache_pos=None,
                 enc_out: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
        """Scan groups.  Returns (hidden, new_caches, aux_loss)."""
        cfg = self.cfg

        def group_body(carry, xs):
            x, aux = carry
            gp, gcache = xs
            x = self._c_hidden(x)
            new_gcache = {} if gcache is not None else None
            for j in range(cfg.period):
                slot = f"slot{j}"
                cache_j = gcache[slot] if gcache is not None else None
                x, nc, a = B.apply_layer(
                    gp[slot], x, cfg, cfg.pattern[j], positions=positions,
                    mode=mode, cache=cache_j, cache_pos=cache_pos,
                    enc_out=enc_out, causal=True)
                aux = aux + a
                if new_gcache is not None:
                    new_gcache[slot] = nc
            return (x, aux), new_gcache

        if cfg.remat and mode == "train":
            group_body = jax.checkpoint(
                group_body, policy=jax.checkpoint_policies.nothing_saveable)

        (x, aux), new_caches = jax.lax.scan(
            group_body, (self._c_hidden(x), jnp.float32(0.0)),
            (params["groups"], caches))
        x = L.rmsnorm(params["final_norm"], self._c_hidden(x), cfg.norm_eps)
        return x, new_caches, aux

    def head(self, params) -> jnp.ndarray:
        """The LM head's [V, D] rows: the embedding unless untied."""
        return params["embed"] if self.cfg.tie_embeddings else \
            params["lm_head"]

    # --------------------------------------------------------------- loss
    def loss(self, params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        cfg = self.cfg
        x = self._embed_tokens(params, batch)
        b, s = batch["tokens"].shape
        positions = self._positions(batch, s, b)
        enc_out = None
        if cfg.enc_dec:
            enc_out = self._encode(params, batch["frames"])
        h, _, aux = self.backbone(params, x, positions, mode="train",
                                  enc_out=enc_out)
        ce = L.chunked_cross_entropy(h, self.head(params), batch["labels"],
                                     chunk=cfg.logits_chunk,
                                     final_softcap=cfg.final_softcap)
        return ce + 0.01 * aux / max(cfg.n_layers, 1)

    # --------------------------------------------------------- train step
    def make_train_step(self, adam: Optional[AdamConfig] = None):
        cfg = self.cfg
        adam = adam or AdamConfig(lr=1e-4, state_dtype=cfg.optimizer_state_dtype)

        def train_step(state: Dict[str, Any], batch: Dict[str, jnp.ndarray]):
            params, opt_state = state["params"], state["opt_state"]
            mb = cfg.microbatches

            if mb == 1:
                loss, grads = jax.value_and_grad(self.loss)(params, batch)
            else:
                def split(v):
                    return v.reshape(mb, v.shape[0] // mb, *v.shape[1:])
                mbatches = jax.tree_util.tree_map(split, batch)

                def acc_body(carry, mb_batch):
                    loss_acc, grad_acc = carry
                    l, g = jax.value_and_grad(self.loss)(params, mb_batch)
                    grad_acc = jax.tree_util.tree_map(jnp.add, grad_acc, g)
                    return (loss_acc + l, grad_acc), None

                zero = jax.tree_util.tree_map(jnp.zeros_like, params)
                (loss, grads), _ = jax.lax.scan(acc_body,
                                                (jnp.float32(0.0), zero),
                                                mbatches)
                loss = loss / mb
                grads = jax.tree_util.tree_map(lambda g: g / mb, grads)

            grads = sanitize(grads)
            grads, gnorm = clip_by_global_norm(grads, 1.0)
            params, opt_state = adam_update(grads, opt_state, params, adam)
            new_state = {"params": params, "opt_state": opt_state,
                         "step": state["step"] + 1}
            return new_state, {"loss": loss, "grad_norm": gnorm}

        return train_step

    def init_train_state(self, key, adam: Optional[AdamConfig] = None):
        adam = adam or AdamConfig(lr=1e-4,
                                  state_dtype=self.cfg.optimizer_state_dtype)
        params = self.init_params(key)
        return {"params": params, "opt_state": adam_init(params, adam),
                "step": jnp.zeros((), jnp.int32)}

    # ------------------------------------------------------------ serving
    def cache_shapes(self, batch: int, seq: int) -> Any:
        cfg = self.cfg
        cross = 1500 if cfg.enc_dec else 0
        out = {}
        for j in range(cfg.period):
            shapes = B.layer_cache_shapes(cfg, cfg.pattern[j], batch, seq,
                                          cross_len=cross)
            out[f"slot{j}"] = shapes
        # add leading group axis
        def with_group(x):
            return (cfg.n_groups,) + tuple(x)
        return jax.tree_util.tree_map(with_group, out,
                                      is_leaf=lambda x: isinstance(x, tuple))

    def init_cache(self, batch: int, seq: int) -> Any:
        cfg = self.cfg
        adt = _dt(cfg.activ_dtype)

        def mk(path_shape):
            return jnp.zeros(path_shape, adt)

        shapes = self.cache_shapes(batch, seq)
        # recurrent states are fp32
        def mk_leaf(path, shape):
            fp32 = any(k in path for k in ("ssm", "S", "n", "c", "h", "conv"))
            return jnp.zeros(shape, jnp.float32 if fp32 else adt)

        out = {}
        for slot, shs in shapes.items():
            out[slot] = {k: mk_leaf(k, v) for k, v in shs.items()}
        return out

    def prefill(self, params, batch: Dict[str, jnp.ndarray], cache_len: int
                ) -> Tuple[Any, jnp.ndarray]:
        """Run the prompt; returns (caches, last-token logits)."""
        cfg = self.cfg
        x = self._embed_tokens(params, batch)
        b, s = batch["tokens"].shape
        positions = self._positions(batch, s, b)
        enc_out = self._encode(params, batch["frames"]) if cfg.enc_dec else None
        caches = self.init_cache(b, cache_len)
        h, new_caches, _ = self.backbone(params, x, positions, mode="prefill",
                                         caches=caches, enc_out=enc_out)
        logits = h[:, -1:].astype(jnp.float32) @ \
            self.head(params).astype(jnp.float32).T
        if cfg.final_softcap:
            logits = L._softcap(logits, cfg.final_softcap)
        return new_caches, logits

    def decode_step(self, params, caches, token: jnp.ndarray,
                    pos: jnp.ndarray) -> Tuple[Any, jnp.ndarray]:
        """One token for the whole batch.  token: [B, 1]; pos scalar."""
        cfg = self.cfg
        x = params["embed"][token].astype(_dt(cfg.activ_dtype))
        b = token.shape[0]
        if cfg.mrope:
            positions = jnp.broadcast_to(pos, (3, b, 1))
        else:
            positions = jnp.broadcast_to(pos, (b, 1))
        h, new_caches, _ = self.backbone(params, x, positions, mode="decode",
                                         caches=caches, cache_pos=pos,
                                         enc_out=None)
        logits = h[:, -1:].astype(jnp.float32) @ \
            self.head(params).astype(jnp.float32).T
        if cfg.final_softcap:
            logits = L._softcap(logits, cfg.final_softcap)
        return new_caches, logits


def build_model(cfg: ArchConfig, mesh=None) -> Model:
    return Model(cfg, mesh=mesh)
