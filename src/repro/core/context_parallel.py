"""Context (sequence) parallelism for Taylor linear attention.

Ring attention for softmax moves O(n·d) KV blocks around the ring every
step.  The Taylor moments are *sums over positions*, so context parallelism
needs exactly ONE exchange of the constant-size state
(O(d²·d_v) per kv head, independent of sequence length):

  1. each shard runs the chunked scan over its local sequence slice with a
     zero initial state, producing local unnormalised (num, den) and its
     local state contribution;
  2. one all-gather of the per-shard states (the only collective);
  3. shard i adds the contraction of its queries against the *exclusive
     prefix sum* of earlier shards' states, then normalises.

This is exact (tested against the unsharded chunked run) and is the
long-context prefill strategy for the 500k cells.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.feature_map import TaylorConfig
from repro.core.taylor import (
    TaylorState,
    _chunk_inter,
    _group,
    _norm_qk,
    _safe_div,
    _ungroup,
    chunked_num_den,
    init_taylor_state,
)

Array = jax.Array


def attention_context_parallel(
    q: Array,
    k: Array,
    v: Array,
    cfg,
    mesh: Mesh,
    axis: str,
    dp_axis=None,
) -> Array:
    """Registry-dispatched context-parallel attention.

    Resolves ``cfg.attention`` (cfg: ``ModelConfig``) through the backend
    registry, enforces the ``supports_cp`` capability flag and delegates to
    the backend's ``apply_cp`` — the one entry point for sequence-sharded
    attention, whatever the backend.  (The taylor implementation below is
    what the built-in backend delegates back to.)
    """
    from repro.backends.registry import resolve_backend  # noqa: PLC0415 (cycle)

    backend = resolve_backend(cfg)
    if not backend.supports_cp:
        raise ValueError(
            f"attention backend {backend.name!r} does not support context "
            "parallelism (supports_cp=False)"
        )
    return backend.apply_cp(q, k, v, cfg, mesh, axis, dp_axis=dp_axis)


def taylor_attention_context_parallel(
    q: Array,
    k: Array,
    v: Array,
    cfg: TaylorConfig,
    mesh: Mesh,
    axis: str,
    chunk: int = 128,
    dp_axis=None,
) -> Array:
    """q: [b, h, n, d]; k/v: [b, hk, n, ·]; sequence sharded over ``axis``,
    batch over ``dp_axis`` (heads replicated within the seq group)."""
    b, h, n, d = q.shape
    h_kv = k.shape[1]
    d_v = v.shape[-1]
    n_shards = mesh.shape[axis]
    assert n % (n_shards * chunk) == 0, (n, n_shards, chunk)
    if dp_axis is not None:
        dp_size = 1
        for a_ in (dp_axis if isinstance(dp_axis, tuple) else (dp_axis,)):
            dp_size *= mesh.shape[a_]
        if b % dp_size != 0:
            dp_axis = None

    def local_fn(q_l, k_l, v_l):
        bl, _, n_loc, _ = q_l.shape
        qn, kn = _norm_qk(q_l, k_l, cfg)
        qg = _group(qn, h_kv)  # [bl, hk, g, n_loc, d]
        g = qg.shape[2]
        nc = n_loc // chunk
        qs = jnp.moveaxis(qg.reshape(bl, h_kv, g, nc, chunk, d), 3, 0)
        ks = jnp.moveaxis(kn.reshape(bl, h_kv, nc, chunk, d), 2, 0)
        vs = jnp.moveaxis(v_l.reshape(bl, h_kv, nc, chunk, d_v), 2, 0)
        state0 = init_taylor_state(bl, h_kv, d, d_v, cfg)
        nums, dens, local_state = chunked_num_den(qs, ks, vs, cfg, state0)
        nums = jnp.moveaxis(nums, 0, 3).reshape(bl, h_kv, g, n_loc, d_v)
        dens = jnp.moveaxis(dens, 0, 3).reshape(bl, h_kv, g, n_loc)

        # the single collective: states of all shards (size O(d²·d_v))
        idx = jax.lax.axis_index(axis)
        gathered = jax.tree_util.tree_map(
            lambda s: jax.lax.all_gather(s, axis) if s is not None else None,
            local_state,
            is_leaf=lambda x: x is None,
        )
        weights = (jnp.arange(n_shards) < idx).astype(jnp.float32)

        def prefix(s):
            if s is None:
                return None
            w = weights.reshape((-1,) + (1,) * (s.ndim - 1))
            return jnp.sum(s * w, axis=0)

        state_in = TaylorState(*(prefix(s) for s in gathered))
        inum, iden = _chunk_inter(qg, state_in, cfg, cfg.scale(d))
        out = _safe_div(nums + inum, dens + iden)
        return _ungroup(out).astype(v.dtype)

    spec = P(dp_axis, None, axis, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
