"""AdamW + cosine schedule + global-norm clipping (no external deps)."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["AdamWConfig", "init_opt_state", "adamw_update", "cosine_lr"]


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000


def cosine_lr(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, step / jnp.maximum(cfg.warmup_steps, 1))
    progress = jnp.clip(
        (step - cfg.warmup_steps) / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
        0.0, 1.0,
    )
    return cfg.lr * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * progress))


def init_opt_state(params: Any) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "mu": jax.tree.map(zeros, params),
        "nu": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def adamw_update(cfg: AdamWConfig, params: Any, grads: Any, state: dict):
    """Returns (new params, new state, metrics)."""
    # Global-norm clip in fp32.
    gnorm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads))
    )
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))
    step = state["step"] + 1
    lr = cosine_lr(cfg, step)
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd_one(p, g, mu, nu):
        g = g.astype(jnp.float32) * scale
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * jnp.square(g)
        mhat = mu / bc1
        nhat = nu / bc2
        delta = mhat / (jnp.sqrt(nhat) + cfg.eps) + cfg.weight_decay * p.astype(
            jnp.float32
        )
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), mu, nu

    # NOTE: chunking this update over the layer-stack axis with lax.map was
    # tried and REFUTED (+36% temp on grok train — the map materializes
    # stacked fp32 ys instead of streaming); plain per-leaf updates fuse
    # better.
    upd = upd_one

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_mu = jax.tree.leaves(state["mu"])
    flat_nu = jax.tree.leaves(state["nu"])
    new = [upd(p, g, m, n) for p, g, m, n in zip(flat_p, flat_g, flat_mu, flat_nu)]
    new_p = jax.tree.unflatten(tdef, [x[0] for x in new])
    new_state = {
        "mu": jax.tree.unflatten(tdef, [x[1] for x in new]),
        "nu": jax.tree.unflatten(tdef, [x[2] for x in new]),
        "step": step,
    }
    return new_p, new_state, {"grad_norm": gnorm, "lr": lr}
