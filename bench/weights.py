"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the plain reference
then reads the same arrays and takes nothing the program made.  The
layout (a nested dict) is the one the program stores; only its shapes
and dtypes are asked of it.  Each leaf is drawn by its name, and every
leaf that ``repro.models.lm_init`` stores for a language model has a
rule:

* matrices: truncated normal (±2σ) with σ = 1/√fan-in (the router and
  the Mamba2 projections too), the embedding table and an untied output
  head with σ = 1/√d (unit-variance logits);
* norm scales and Mamba2's skip ``D``: 1 + N(0, 0.05²), so the reference
  has to apply them;
* biases (q/k/v, MLP, Mamba2's conv): N(0, 0.1²), large enough to matter
  after the q/k LayerNorm;
* Mamba2's depthwise conv: truncated normal with σ = 1/√conv_width;
  ``A_log``: log U[1, 16], so that A = −exp(A_log) is negative;
  ``dt_bias``: softplus⁻¹ of a dt drawn log-uniformly in
  [``DT_MIN``, ``DT_MAX``] (the published ``time_step_min`` and
  ``time_step_max``).

Leaf ``i`` of the flattened layout draws from ``fold_in(key, i)``, so a
rule added for one name leaves the draws of every other leaf as they
were.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# fan-in of each matrix from its shape (leading layer and expert axes aside)
FAN_IN = {
    "wq": lambda s: s[-3], "wk": lambda s: s[-3], "wv": lambda s: s[-3],
    "wo": lambda s: s[-3] * s[-2],
    "w_gate": lambda s: s[-2], "w_up": lambda s: s[-2], "w_down": lambda s: s[-2],
    "router": lambda s: s[-2], "in_proj": lambda s: s[-2], "out_proj": lambda s: s[-2],
    "conv_w": lambda s: s[-2],  # [conv_width, channels]: depthwise
}
BIASES = ("b", "b_up", "b_down", "conv_b")
DT_MIN, DT_MAX = 1e-3, 1e-1


def _names(path) -> tuple:
    return tuple(getattr(k, "key", getattr(k, "name", str(k))) for k in path)


def _leaf(names: tuple, shape, key):
    """One leaf drawn by its path ``names`` (float32)."""
    last = names[-1]
    if last in ("scale", "D"):
        return 1.0 + 0.05 * jax.random.normal(key, shape)
    if last in BIASES:
        return 0.1 * jax.random.normal(key, shape)
    if last == "A_log":
        return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))
    if last == "dt_bias":
        u = jax.random.uniform(key, shape, minval=math.log(DT_MIN), maxval=math.log(DT_MAX))
        dt = jnp.exp(u)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus⁻¹(dt)
    owner = names[-2] if last == "w" else last
    if owner in ("embed", "unembed"):
        std = shape[-1] ** -0.5
    elif owner in FAN_IN:
        std = FAN_IN[owner](shape) ** -0.5
    else:
        raise ValueError(f"no rule for weight {'.'.join(names)}")
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape)


def layout(cfg, dtype):
    """Shapes and dtypes of the program's parameter tree for ``cfg`` in
    ``dtype`` (leaves the program keeps in float32 stay so)."""
    from repro.models import lm_init  # noqa: PLC0415

    return jax.eval_shape(lambda k: lm_init(k, cfg, dtype=dtype),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def make(shapes, seed: int):
    """The weights for ``shapes`` (a tree of ``ShapeDtypeStruct``) from
    ``seed``, each leaf in its own dtype, built on the default device by
    one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def build(key):
        leaves = [
            _leaf(_names(path), s.shape, jax.random.fold_in(key, i)).astype(s.dtype)
            for i, (path, s) in enumerate(flat)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(seed_key(seed))
