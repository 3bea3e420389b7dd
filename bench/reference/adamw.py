"""AdamW with global-norm clipping, written from its published description
(Loshchilov & Hutter, "Decoupled Weight Decay Regularization"), with the
learning rate warmed up linearly and then decayed on a cosine.  Imports
nothing of the program; the hyperparameters come from the configuration
file.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def learning_rate(opt: dict, step: int) -> float:
    """Learning rate of 1-based ``step``: linear warm-up over ``warmup``
    steps, then a cosine from ``lr`` down to ``final_frac * lr`` at
    ``total_steps``."""
    warm = min(step / max(opt["warmup"], 1), 1.0)
    prog = min(max((step - opt["warmup"]) / max(opt["total_steps"] - opt["warmup"], 1), 0.0), 1.0)
    frac = opt["final_frac"]
    return opt["lr"] * warm * (frac + (1 - frac) * 0.5 * (1 + math.cos(math.pi * prog)))


def init(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"m": jax.tree.map(zeros, params), "v": jax.tree.map(zeros, params)}


def clip(opt: dict, grads):
    """Gradients scaled so that their global norm is at most ``clip_norm``."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def step(opt: dict, t: int, params, state, grads, dtype=jnp.float32):
    """One AdamW step at 1-based step ``t`` on clipped ``grads``; returns
    ``(params, state)``.  Params are held in ``dtype``."""
    lr = learning_rate(opt, t)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    c1, c2 = 1 - b1**t, 1 - b2**t

    def upd(p, m, v):
        p32 = p.astype(jnp.float32)
        new = p32 - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p32)
        return new.astype(dtype)

    return jax.tree.map(upd, params, m, v), {"m": m, "v": v}
