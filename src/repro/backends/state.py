"""Decode-state containers shared by the attention backends.

These used to live in ``repro.models.attention``; they sit below the
backend implementations now so that ``backends/*`` can construct them
without importing the model layer (``models/attention`` re-exports them
for compatibility).
"""

from __future__ import annotations

from typing import NamedTuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import TaylorState

Array = jax.Array


def tree_slot_health(tree) -> Array:
    """Per-batch-row finiteness of a decode-state pytree.

    The generic building block of the backends' ``state_health`` hooks
    (serving corruption guards — docs/serving.md §Failure semantics):
    every inexact-dtype leaf is checked with ``jnp.isfinite`` reduced over
    its non-batch axes; integer leaves (e.g. ``KVCache.length``) are
    skipped — bounds on those are backend semantics, not finiteness.

    Args:
      tree: decode-state pytree whose array leaves share a leading batch
        (serving-slot) axis.

    Returns:
      ``[b]`` bool — True where every leaf of that row is finite.
    """
    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(jnp.asarray(l).dtype, jnp.inexact)]
    if not leaves:
        return jnp.asarray(True)
    ok = None
    for l in leaves:
        h = jnp.isfinite(l).reshape(l.shape[0], -1).all(axis=-1)
        ok = h if ok is None else ok & h
    return ok


class KVCache(NamedTuple):
    """Ring-less fixed-capacity KV cache (softmax / linear_elu backends).

    ``length`` is per batch row ([b] int32): in slotted serving every slot
    decodes at its own position, so the number of valid cache entries is a
    per-slot quantity (see repro/serve/slots.py)."""

    k: Array  # [b, hk, n_max, hd]
    v: Array  # [b, hk, n_max, hd]
    length: Array  # [b] int32 — valid tokens written per batch row/slot


AttnCache = Union[KVCache, TaylorState]


class CrossCache(NamedTuple):
    """Precomputed cross-attention source: either projected K/V (KV-kind
    backends) or the global TaylorState (moments-kind backends)."""

    kv: AttnCache


class StackedLeaf(NamedTuple):
    """One leaf of a run's stacked decode cache, handed WHOLE to
    ``decode_step`` in place of one layer's slice of it (the leaf that
    ``AttentionBackend.in_place_leaf`` names): the backend advances layer
    ``layer`` of ``stack`` in place and returns the whole updated stack in
    that field of the new cache."""

    stack: Array  # [n_groups, run_len, b, ...]
    layer: Array  # [2] int32: (group, position in the run)
    keep: Array  # [b] bool: rows that advance; the others stay bit for bit


class QuantizedLeaf(NamedTuple):
    """One quantised decode-state tensor + its dequantisation scale.

    ``q`` holds the payload in the storage dtype (int8 or
    ``float8_e4m3fn``); ``scale`` is fp32 with the same leading
    (slot/head) axes and size-1 trailing axes, so ``q * scale``
    broadcasts back to the dense leaf.  Scales are exact powers of two
    (see ``quantize_leaf``), which makes decode→encode→decode value
    round-trips bit-exact — the property the serve layer's snapshot
    handoff (preemption / speculative rollback) relies on."""

    q: Array
    scale: Array


class PagedKVCache(NamedTuple):
    """Page-pool form of one ``KVCache`` node (serve layer only).

    ``k_pages``/``v_pages`` are ``[*lead, total_pages, hk, page_size,
    hd]`` where ``*lead`` are the group stacking axes (``[n_groups,
    run_len]``) or empty for tail nodes.  Which pages belong to which
    serve slot lives in the single top-level ``PagedMeta`` of the slot
    cache — every paged node shares one page table.  Free pages are kept
    ZERO (pool init + clear both zero them), so gathering an unallocated
    page id is equivalent to reading an unwritten dense cache row."""

    k_pages: Array
    v_pages: Array


class PagedMeta(NamedTuple):
    """Shared page table + per-slot lengths of a paged slot cache.

    ``table`` is ``[slots, pages_per_slot]`` int32 with ``-1`` marking an
    unallocated entry (allocated entries form a prefix of each row);
    ``length`` is ``[slots]`` int32 — the per-slot valid-token count every
    dense ``KVCache.length`` of the decoded tree broadcasts from."""

    table: Array
    length: Array


# Payload bound per quantised storage dtype: the scale is the power of two
# 2**e with amax / TOP in [2**(e-1), 2**e), so payload magnitudes land in
# [TOP/2, TOP).  int8: TOP = 128 (round-to-int, clip at 127).  fp8 e4m3:
# TOP = 248, the midpoint between e4m3's 240 and 256, so round-to-nearest
# never rounds a payload up to 256 — the decoded amax stays in the same
# binade and re-encoding a decoded leaf is bit-exact, while no element is
# clipped (a [128, 256) payload range would have to clip (240, 256) to 240,
# twice e4m3's rounding error, on each head's largest moments).
_QTOP = {"int8": 128.0, "fp8": 248.0}


def quantize_leaf(x: Array, n_lead: int, qdtype: str) -> QuantizedLeaf:
    """Quantise one dense state leaf with per-head pow2 scales.

    The scale for each leading-axes index (slot, kv head, …) is the
    power of two ``2**frexp(amax / TOP)[1]`` — exact, so dequantised
    values re-encode to themselves bit-for-bit: the serve layer may
    decode, splice, and re-encode a slot cache any number of times
    (snapshot handoff, verify rounds) without drift.  Non-finite ``amax``
    propagates into the scale, so corrupted state stays visible to
    ``state_health`` after the round-trip.

    Args:
      x: dense leaf; axes ``< n_lead`` are kept (slot/head), the rest are
        reduced into one amax per head.
      n_lead: number of leading axes to keep per-scale.
      qdtype: ``"int8"`` or ``"fp8"``.

    Returns:
      ``QuantizedLeaf`` with ``q`` in the storage dtype and fp32
      ``scale`` shaped like ``x`` with size-1 reduced axes.
    """
    xf = x.astype(jnp.float32)
    axes = tuple(range(n_lead, x.ndim))
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    _, e = jnp.frexp(amax / _QTOP[qdtype])
    scale = jnp.exp2(e.astype(jnp.float32))
    scale = jnp.where(jnp.isfinite(amax), scale, amax)
    y = xf / scale
    if qdtype == "int8":
        q = jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    else:
        q = jnp.clip(y, -240.0, 240.0).astype(jnp.float8_e4m3fn)
    return QuantizedLeaf(q=q, scale=scale)


def dequantize_leaf(leaf: QuantizedLeaf, dtype=jnp.float32) -> Array:
    """Dense fp leaf from a ``QuantizedLeaf`` (``q * scale``).

    Args:
      leaf: quantised leaf from ``quantize_leaf``.
      dtype: output dtype (fp32 for the Taylor moment state — absorbs
        and reads always accumulate full precision).

    Returns:
      Dense array of ``leaf.q.shape`` in ``dtype``.
    """
    return (leaf.q.astype(jnp.float32) * leaf.scale).astype(dtype)


def gather_pages(pages: Array, table: Array, n_max: int) -> Array:
    """Decode one paged pool leaf to its dense ``[*lead, slots, hk,
    n_max, hd]`` form.

    Unallocated table entries (``-1``) read as zeros — identical to an
    unwritten dense cache row (free pages are also kept zero, so the
    clamp-gather never leaks another slot's tokens).

    Args:
      pages: ``[*lead, total_pages, hk, page_size, hd]`` pool.
      table: ``[slots, pages_per_slot]`` int32 page table (-1 = free).
      n_max: dense per-slot capacity (``pages_per_slot * page_size`` may
        overshoot it; the tail is sliced off).

    Returns:
      Dense ``[*lead, slots, hk, n_max, hd]`` array.
    """
    lead = pages.ndim - 4
    total, hk, ps, hd = pages.shape[lead:]
    slots, pp = table.shape
    flat = table.reshape(-1)
    out = jnp.take(pages, jnp.clip(flat, 0, total - 1), axis=lead)
    valid = (flat >= 0).reshape((1,) * lead + (slots * pp, 1, 1, 1))
    out = jnp.where(valid, out, jnp.zeros((), pages.dtype))
    out = out.reshape(pages.shape[:lead] + (slots, pp, hk, ps, hd))
    out = jnp.swapaxes(out, lead + 1, lead + 2)
    out = out.reshape(pages.shape[:lead] + (slots, hk, pp * ps, hd))
    return out[..., :n_max, :]


def scatter_pages(dense: Array, pages: Array, table: Array) -> Array:
    """Encode one dense ``[*lead, slots, hk, n_max, hd]`` leaf back into
    its page pool.

    The inverse of ``gather_pages`` over allocated entries: each slot's
    token rows are split into pages and scattered to that slot's table
    ids; rows belonging to unallocated entries are DROPPED (out-of-range
    scatter), so a slot can never write outside its own pages.

    Args:
      dense: dense leaf (dtype is cast to the pool's).
      pages: current ``[*lead, total_pages, hk, page_size, hd]`` pool.
      table: ``[slots, pages_per_slot]`` int32 page table (-1 = free).

    Returns:
      Updated pool; pages of other slots (and free pages) bit-identical.
    """
    lead = dense.ndim - 4
    total, hk, ps, hd = pages.shape[lead:]
    slots, pp = table.shape
    n_max = dense.shape[lead + 2]
    pad = pp * ps - n_max
    if pad:
        width = [(0, 0)] * dense.ndim
        width[lead + 2] = (0, pad)
        dense = jnp.pad(dense, width)
    x = dense.reshape(dense.shape[:lead] + (slots, hk, pp, ps, hd))
    x = jnp.swapaxes(x, lead + 1, lead + 2)
    x = x.reshape(dense.shape[:lead] + (slots * pp, hk, ps, hd))
    flat = table.reshape(-1)
    ids = jnp.where(flat >= 0, flat, total)  # out of range -> dropped
    p = jnp.moveaxis(pages, lead, 0)
    vals = jnp.moveaxis(x, lead, 0).astype(pages.dtype)
    p = p.at[ids].set(vals, mode="drop")
    return jnp.moveaxis(p, 0, lead)


def kv_cache_pspec() -> KVCache:
    """Logical partition axes of a ``KVCache`` (the ``state_kind="kv"``
    decode-state sharding: slots over "dp", kv heads over "tp").

    Used by ``AttentionBackend.cache_pspec``'s default implementation and
    resolved against a concrete mesh by
    ``distributed.sharding.slot_cache_specs`` (divisibility-aware — e.g.
    MQA's single kv head drops "tp" and the resolver falls back to the
    last dim).

    Returns:
      ``KVCache`` whose leaves are logical ``PartitionSpec``s for
      ``k [b, hk, n_max, hd]``, ``v`` (same) and ``length [b]``.
    """
    return KVCache(
        k=P("dp", "tp", None, None),
        v=P("dp", "tp", None, None),
        length=P("dp"),
    )
