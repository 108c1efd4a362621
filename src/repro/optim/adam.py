"""Adam(W) on pytrees with configurable state dtype.

``state_dtype="bfloat16"`` (or ``"int8"`` via optim.compress quantizers)
halves/quarters optimizer memory — required to fit the ≥100B assigned
architectures on 16 GB v5e chips (see DESIGN.md §6); the update math is
always performed in float32.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs import jaxprof


@dataclasses.dataclass(frozen=True)
class AdamConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    state_dtype: Optional[str] = None   # None -> same as param dtype


class AdamState(NamedTuple):
    step: jnp.ndarray
    mu: Any
    nu: Any


def _cast(tree, dtype):
    if dtype is None:
        return tree
    dt = jnp.dtype(dtype)
    return jax.tree_util.tree_map(lambda x: x.astype(dt), tree)


def adam_init(params, cfg: AdamConfig) -> AdamState:
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return AdamState(step=jnp.zeros((), jnp.int32),
                     mu=_cast(zeros, cfg.state_dtype),
                     nu=_cast(zeros, cfg.state_dtype))


# jitted so an eagerly-run update (the segmented PPO path) compiles once:
# eager ``b1 ** step`` with a concrete step would compile a new
# integer_pow program for every step value
@partial(jax.jit, static_argnames=("cfg",))
def adam_update(grads, state: AdamState, params, cfg: AdamConfig,
                lr_scale: jnp.ndarray | float = 1.0):
    step = state.step + 1
    b1, b2 = cfg.b1, cfg.b2

    def upd(g, m, v, p):
        g32 = g.astype(jnp.float32)
        m32 = m.astype(jnp.float32) * b1 + (1 - b1) * g32
        v32 = v.astype(jnp.float32) * b2 + (1 - b2) * g32 * g32
        mhat = m32 / (1 - b1 ** step)
        vhat = v32 / (1 - b2 ** step)
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay:
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        new_p = p.astype(jnp.float32) - cfg.lr * lr_scale * delta
        return (new_p.astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype))

    out = jax.tree_util.tree_map(upd, grads, state.mu, state.nu, params)
    new_params = jax.tree_util.tree_map(lambda t: t[0], out,
                                        is_leaf=lambda t: isinstance(t, tuple))
    new_mu = jax.tree_util.tree_map(lambda t: t[1], out,
                                    is_leaf=lambda t: isinstance(t, tuple))
    new_nu = jax.tree_util.tree_map(lambda t: t[2], out,
                                    is_leaf=lambda t: isinstance(t, tuple))
    return new_params, AdamState(step=step, mu=new_mu, nu=new_nu)


jaxprof.register("optim.adam_update", adam_update)
