"""Logical-axis sharding API.

Models annotate activations with *logical* axis names ("dp", "tp", "sp",
"ep", None).  A ``sharding_rules`` context binds logical names to physical
mesh axes; outside the context the annotations are no-ops (CPU tests run
unsharded).  Parameters get their PartitionSpecs from rule-based path
matching in distributed/sharding.py.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import get_abstract_mesh

Array = jax.Array

# logical name -> physical mesh axis (or tuple of axes)
Rules = Mapping[str, Union[str, Tuple[str, ...], None]]

# Default logical names:
#   dp  — data parallel (batch dim)           -> ("pod", "data") on prod meshes
#   fsdp— parameter sharding dim              -> "data" (and "pod" for XXL)
#   tp  — tensor parallel (heads / ffn / vocab)-> "model"
#   ep  — expert parallel                     -> "model"
#   sp  — sequence/context parallel           -> (off by default)

_ACTIVE: contextvars.ContextVar[Optional[Tuple[Mesh, Rules]]] = contextvars.ContextVar(
    "repro_sharding_rules", default=None
)


# "sp" = Megatron-style sequence parallelism: residual-stream activations
# (the tensors remat saves at layer boundaries) are sharded along the
# sequence dim over the TP group; XLA inserts the all-gather/reduce-scatter
# pair around each block (the classic SP g/ḡ operators).
DEFAULT_RULES: Rules = {
    "dp": ("pod", "data"),
    "fsdp": "data",
    "tp": "model",
    "ep": "model",
    "sp": "model",
}

SINGLE_POD_RULES: Rules = {
    "dp": "data",
    "fsdp": "data",
    "tp": "model",
    "ep": "model",
    "sp": "model",
}


def rules_for_mesh(mesh: Mesh, **overrides) -> Rules:
    base = dict(DEFAULT_RULES if "pod" in mesh.axis_names else SINGLE_POD_RULES)
    base.update(overrides)
    return base


@contextlib.contextmanager
def sharding_rules(mesh: Mesh, rules: Optional[Rules] = None):
    """Bind logical names to ``mesh`` axes and make ``mesh`` the current
    mesh (``jax.set_mesh``) for every ``jit``/``shard_map`` traced inside."""
    token = _ACTIVE.set((mesh, rules if rules is not None else rules_for_mesh(mesh)))
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        _ACTIVE.reset(token)


def active() -> Optional[Tuple[Mesh, Rules]]:
    return _ACTIVE.get()


def logical_to_spec(axes: Sequence[Optional[str]], rules: Rules) -> P:
    resolved = []
    for name in axes:
        if name is None:
            resolved.append(None)
        elif name == "*":  # leave to the SPMD partitioner
            resolved.append(P.UNCONSTRAINED)
        else:
            resolved.append(rules.get(name))
    return P(*resolved)


def constrain(x: Array, *axes: Optional[str]) -> Array:
    """Annotate activation x with logical axes; no-op outside a rules context
    or under vmap-induced rank mismatch.  Axes whose dim size is not
    divisible by the physical axis size are dropped (e.g. batch=1 decode,
    whisper's 1500-frame encoder)."""
    ctx = _ACTIVE.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    if len(axes) != x.ndim:
        return x
    # inside shard_map (Manual axes) constraints are meaningless/illegal
    am = get_abstract_mesh()
    if not am.empty and AxisType.Manual in am.axis_types:
        return x
    resolved = []
    for name, size in zip(axes, x.shape):
        if name == "*":  # dim left to the SPMD partitioner
            resolved.append(P.UNCONSTRAINED)
            continue
        phys = rules.get(name) if name else None
        if phys is not None and size % mesh_axis_size(mesh, phys) != 0:
            phys = None
        resolved.append(phys)
    # one physical axis may appear only once in a spec
    seen = set()
    final = []
    for phys in resolved:
        if phys is P.UNCONSTRAINED:
            final.append(phys)
            continue
        key = tuple(phys) if isinstance(phys, tuple) else phys
        if phys is not None and key in seen:
            phys = None
        if phys is not None:
            seen.add(key)
        final.append(phys)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*final)))


def mesh_axis_size(mesh: Mesh, name: Union[str, Tuple[str, ...], None]) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= mesh.shape[n]
        return out
    return mesh.shape[name]

