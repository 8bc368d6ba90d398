"""AdamW with global-norm clipping and LR schedules (pure JAX; the paper
trains the actor with AdamW lr 5e-7, wd 0.01, clip 1.0 — Appendix A.1)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 5e-7
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    clip_norm: float = 1.0
    schedule: str = "constant"       # constant|cosine|warmup_cosine
    total_steps: int = 1000
    warmup_steps: int = 0


def lr_at(cfg: AdamWConfig, step) -> jnp.ndarray:
    step = jnp.asarray(step, jnp.float32)
    lr = jnp.asarray(cfg.lr, jnp.float32)
    if cfg.schedule == "constant":
        return lr
    warm = jnp.where(cfg.warmup_steps > 0,
                     jnp.minimum(1.0, step / jnp.maximum(cfg.warmup_steps, 1)),
                     1.0)
    t = jnp.clip((step - cfg.warmup_steps)
                 / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * t))
    if cfg.schedule == "cosine":
        return lr * cos
    return lr * warm * cos


def init(params) -> Dict[str, Any]:
    """Moments are float32 whatever the parameter dtype: ``update`` returns
    them in float32, so any other start would give the update step a second
    signature (and a second compile) on step 2."""
    def zeros(p):
        return jnp.zeros(jnp.shape(p), jnp.float32)
    return {"mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def update(cfg: AdamWConfig, params, grads, state) -> Tuple[Any, Dict[str, Any],
                                                            Dict[str, jnp.ndarray]]:
    """Returns (new_params, new_state, info)."""
    gnorm = global_norm(grads)
    scale = jnp.where(gnorm > cfg.clip_norm, cfg.clip_norm / (gnorm + 1e-9), 1.0)
    grads = jax.tree.map(lambda g: g * scale, grads)

    step = state["step"] + 1
    lr = lr_at(cfg, step)
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m = cfg.b1 * m + (1 - cfg.b1) * g32
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g32)
        mh = m / b1c
        vh = v / b2c
        step_ = lr * (mh / (jnp.sqrt(vh) + cfg.eps)
                      + cfg.weight_decay * p.astype(jnp.float32))
        return (p.astype(jnp.float32) - step_).astype(p.dtype), m, v

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["mu"])
    flat_v = jax.tree.leaves(state["nu"])
    new_p, new_m, new_v = [], [], []
    for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
        np_, nm, nv = upd(p, g, m, v)
        new_p.append(np_), new_m.append(nm), new_v.append(nv)
    new_params = jax.tree.unflatten(tdef, new_p)
    new_state = {"mu": jax.tree.unflatten(tdef, new_m),
                 "nu": jax.tree.unflatten(tdef, new_v), "step": step}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
