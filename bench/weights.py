"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the plain reference
then reads the same arrays and takes nothing the program made.  The
layout (a nested dict) is the one the program stores; only its shapes
are asked of it.  Each leaf is drawn by its name:

* matrices: truncated normal (±2σ) with σ = 1/√fan-in, the embedding
  table with σ = 1/√d (unit-variance logits through the tied head);
* norm scales: 1 + N(0, 0.05²), so the reference has to apply them;
* q/k/v biases: N(0, 0.1²), large enough to matter after the q/k
  LayerNorm.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# fan-in of each matrix from its shape (leading layer axes aside)
FAN_IN = {
    "wq": lambda s: s[-3], "wk": lambda s: s[-3], "wv": lambda s: s[-3],
    "wo": lambda s: s[-3] * s[-2],
    "w_gate": lambda s: s[-2], "w_up": lambda s: s[-2], "w_down": lambda s: s[-2],
}


def _names(path) -> tuple:
    return tuple(getattr(k, "key", getattr(k, "name", str(k))) for k in path)


def _leaf(names: tuple, shape, key):
    """One leaf drawn by its path ``names`` (float32)."""
    last = names[-1]
    if last == "scale":
        return 1.0 + 0.05 * jax.random.normal(key, shape)
    if last == "b":
        return 0.1 * jax.random.normal(key, shape)
    owner = names[-2] if last == "w" else last
    if owner == "embed":
        std = shape[-1] ** -0.5
    elif owner in FAN_IN:
        std = FAN_IN[owner](shape) ** -0.5
    else:
        raise ValueError(f"no rule for weight {'.'.join(names)}")
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape)


def layout(cfg, dtype):
    """Shapes of the program's parameter tree for ``cfg`` in ``dtype``."""
    from repro.models import lm_init  # noqa: PLC0415

    return jax.eval_shape(lambda k: lm_init(k, cfg, dtype=dtype),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def make(shapes, seed: int, dtype):
    """The weights for ``shapes`` (a tree of ``ShapeDtypeStruct``) from
    ``seed``, in ``dtype``, built on the default device by one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def build(key):
        leaves = [
            _leaf(_names(path), s.shape, jax.random.fold_in(key, i)).astype(dtype)
            for i, (path, s) in enumerate(flat)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(seed_key(seed))
