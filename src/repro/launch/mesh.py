"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device query, and tests must keep seeing 1 CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the partitioner places
    what the ``NamedSharding``s and ``constrain`` annotations leave open
    (``make_mesh`` otherwise defaults to ``Explicit`` axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """v5e production topology: one pod = 16×16 = 256 chips as
    ("data", "model"); multi-pod = 2 pods = 512 chips with a leading "pod"
    axis (data-parallel across pods over DCN/ICI)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (CPU tests, examples)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return _auto_mesh((data, model), ("data", "model"))


def make_serve_mesh(slots: int = 1, model: int = 1) -> Mesh:
    """Serving mesh: ("data", "model") where "data" shards the SLOT axis of
    the serve engine's decode cache (continuous batching: each device group
    owns a contiguous run of slots) and "model" carries tensor parallelism
    over the weights via the same ``param_specs`` rules training uses.

    The axis names deliberately match ``make_host_mesh`` so
    ``rules_for_mesh`` applies unchanged (serving binds "dp" to the slot
    axis instead of the batch axis — same logical name, see
    docs/serving.md §Sharding).  A 1×1 mesh is the degenerate single-device
    engine, bit-identical to running without a mesh.

    Unlike ``make_host_mesh`` this REFUSES to shrink silently: a serving
    deployment that comes up on the wrong topology should fail loudly, not
    serve at a fraction of the provisioned capacity.
    """
    n = len(jax.devices())
    if slots * model > n:
        raise ValueError(
            f"make_serve_mesh({slots}×{model}) needs {slots * model} "
            f"devices but only {n} are visible"
        )
    return _auto_mesh((slots, model), ("data", "model"))
