"""Mixture-of-Experts FFN.

Two dispatch implementations behind one API:

  * ``dense``  — capacity-free einsum dispatch through one-hot combine
    weights.  Compute O(tokens · E · d · ff) — exact but wasteful; used for
    tiny smoke/tests on CPU and as the correctness oracle.
  * ``ep``     — production path: experts sharded over the "ep" (= model)
    mesh axis, tokens routed with fixed expert capacity (cumsum-based,
    sort-free) and exchanged with all_to_all inside ``shard_map``.
    Compute O(tokens · top_k · d · ff) + all-to-all bytes (visible in the
    dry-run collective roofline term).

Routing: softmax-of-logits top-k with renormalised gates; optional shared
experts (Qwen-MoE / Kimi style) always active.  A load-balancing auxiliary
loss (Switch-style) is returned for the train loop.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import api as dist
from repro.models.config import ModelConfig, MoEConfig
from repro.models.layers import dense_init, mlp_apply, mlp_init

Array = jax.Array


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    expert_keys = jax.random.split(ks[0], m.n_experts)
    experts = jax.vmap(lambda k: mlp_init(k, d, m.d_ff_expert, cfg.act, dtype))(
        expert_keys
    )
    params = {
        "router": dense_init(ks[1], (d, m.n_experts), dtype=jnp.float32),
        "experts": experts,  # leaves stacked [E, ...]
    }
    if m.n_shared_experts:
        params["shared"] = mlp_init(ks[2], d, m.d_ff_shared, cfg.act, dtype)
    return params


def _route(params, x: Array, m: MoEConfig) -> Tuple[Array, Array, Array]:
    """Returns (gates [t, top_k], idx [t, top_k], aux_loss scalar) for
    flattened tokens x [t, d]."""
    logits = jnp.einsum(
        "td,de->te", x.astype(jnp.float32), params["router"]["w"]
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # Switch-transformer load-balance loss: E * Σ_e f_e · p_e
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(idx[:, 0], m.n_experts, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)
    aux = m.n_experts * jnp.sum(me * ce)
    return gates, idx, aux


def _moe_dense(params, x: Array, cfg: ModelConfig) -> Tuple[Array, Array]:
    """Oracle path: every expert sees every token, one-hot-masked combine."""
    m = cfg.moe
    t, d = x.shape
    gates, idx, aux = _route(params, x, m)
    # combine[t, e] = gate of expert e for token t (0 if not selected)
    combine = jnp.zeros((t, m.n_experts), jnp.float32)
    combine = combine.at[jnp.arange(t)[:, None], idx].set(gates)

    def run_expert(ep):
        return mlp_apply(ep, x, cfg.act)  # [t, d]

    outs = jax.vmap(run_expert)(params["experts"])  # [E, t, d]
    y = jnp.einsum("etd,te->td", outs.astype(jnp.float32), combine)
    return y.astype(x.dtype), aux


def _capacity(m: MoEConfig, tokens_per_shard: int, n_local_experts: int) -> int:
    cap = int(m.capacity_factor * tokens_per_shard * m.top_k / m.n_experts)
    cap = max(cap, 4)
    # round up to an MXU-friendly multiple of 8
    return ((cap + 7) // 8) * 8


def moe_apply(
    params, x: Array, cfg: ModelConfig
) -> Tuple[Array, Array]:
    """x: [b, n, d] → (y [b, n, d], aux loss scalar).

    Implementations (cfg.moe.impl):
      "dense"   — oracle einsum over all experts (tests).
      "ep"      — global capacity-einsum dispatch (small scale, no mesh).
      "ep_a2a"  — production path: shard_map over (dp × ep) with sort-based
                  local dispatch, all_to_all exchange, FSDP all-gather of
                  expert weights.  Selected automatically under "auto" when
                  a sharding-rules context is active.
    """
    m = cfg.moe
    b, n, d = x.shape
    impl = m.impl
    ctx = dist.active()
    if impl == "auto":
        impl = "ep_a2a" if ctx is not None else "dense"
    if impl == "ep_a2a" and ctx is None:
        impl = "ep"
    if impl == "ep_a2a":
        mesh, rules = ctx
        y, aux = _moe_ep_a2a(params, x, cfg, mesh, rules)
    elif impl == "dense":
        y, aux = _moe_dense(params, x.reshape(b * n, d), cfg)
        y = y.reshape(b, n, d)
    elif impl == "ep":
        y, aux = _moe_ep_capacity(params, x.reshape(b * n, d), cfg)
        y = y.reshape(b, n, d)
    else:
        raise ValueError(f"unknown moe impl {impl!r}")
    if m.n_shared_experts:
        y = y + mlp_apply(params["shared"], x, cfg.act)
    return y, aux


def _a2a_maybe_quant(x: Array, ep, split_axis: int, concat_axis: int, quant: str):
    """all_to_all, optionally with int8 payload (per-row absmax scales,
    straight-through gradients; the backward exchange stays full precision)."""
    if quant != "int8":
        return jax.lax.all_to_all(
            x, ep, split_axis=split_axis, concat_axis=concat_axis, tiled=True
        )

    @jax.custom_vjp
    def fwd(x):
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-8
        qi = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        qr = jax.lax.all_to_all(
            qi, ep, split_axis=split_axis, concat_axis=concat_axis, tiled=True
        )
        sr = jax.lax.all_to_all(
            scale.astype(jnp.float32), ep, split_axis=split_axis,
            concat_axis=concat_axis, tiled=True,
        )
        return qr.astype(x.dtype) * sr.astype(x.dtype)

    def fwd_rule(x):
        return fwd(x), None

    def bwd_rule(_, g):
        return (
            jax.lax.all_to_all(
                g, ep, split_axis=concat_axis, concat_axis=split_axis, tiled=True
            ),
        )

    fwd.defvjp(fwd_rule, bwd_rule)
    return fwd(x)


def _sort_positions(e_flat: Array, n_experts: int) -> Array:
    """Position of each routed (token, k) inside its expert's buffer —
    sort-based (O(t·K log) and O(t·K) memory, vs the O(t·K·E) one-hot
    cumsum)."""
    tk = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)
    counts = jnp.bincount(e_flat, length=n_experts)
    starts = jnp.cumsum(counts) - counts  # exclusive prefix
    pos_sorted = jnp.arange(tk) - starts[e_flat[order]]
    return jnp.zeros((tk,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))


def _moe_ep_a2a(params, x: Array, cfg: ModelConfig, mesh, rules) -> Tuple[Array, Array]:
    """Expert parallelism via shard_map: tokens stay sharded over dp, expert
    weights over (ep × fsdp).  Per token-chunk (bounding the dispatch buffer
    to ~t_c·K·d):

      route → sort-based positions → scatter into [E, C, d] buffers →
      all_to_all over ep (each shard keeps its experts) → FSDP all-gather of
      the local experts' weights → batched expert MLP → reverse all_to_all →
      gather-combine with gates.

    The chunk loop is remat'd so backward recomputes dispatch buffers
    instead of saving them per chunk.  Experts are zero-padded to a multiple
    of the ep axis (e.g. qwen2-moe 60 → 64; padded experts are unroutable).
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    b, n, d = x.shape
    dp = rules.get("dp")
    ep = rules.get("ep")
    fsdp = rules.get("fsdp")
    dp_size = dist.mesh_axis_size(mesh, dp)
    ep_size = dist.mesh_axis_size(mesh, ep)
    if ep is None or ep_size == 1:
        y, aux = _moe_ep_capacity(params, x.reshape(b * n, d), cfg)
        return y.reshape(b, n, d), aux
    if dp is not None and b % dp_size != 0:
        dp = None
        dp_size = 1
    e_pad = ((m.n_experts + ep_size - 1) // ep_size) * ep_size
    t_loc = (b // dp_size) * n
    # chunk tokens so the dispatch buffer (t_c · K · d) stays ~256 MB
    target = max(1, int(256e6 // (m.top_k * d * 4)))
    n_chunks = 1
    while t_loc // n_chunks > target or t_loc % n_chunks:
        n_chunks += 1
    t_c = t_loc // n_chunks
    cap = _capacity(m, t_c, e_pad)

    router_w = params["router"]["w"]
    experts = params["experts"]
    if e_pad != m.n_experts:  # e.g. qwen2-moe: 60 experts -> 64 over ep=16
        experts = jax.tree_util.tree_map(
            lambda w: jnp.pad(w, ((0, e_pad - m.n_experts),) + ((0, 0),) * (w.ndim - 1)),
            experts,
        )
    fsdp_axes = fsdp if fsdp is not None else ()

    def local(x_l, router_l, experts_l):
        # x_l [b_loc, n, d]; router_l [d/fsdp, E]; experts_l [E/ep, d/fsdp, ·]
        if fsdp_axes:
            router_full = jax.lax.all_gather(router_l, fsdp_axes, axis=0, tiled=True)
            experts_full = jax.tree_util.tree_map(
                lambda w: jax.lax.all_gather(w, fsdp_axes, axis=1, tiled=True),
                experts_l,
            )
        else:
            router_full, experts_full = router_l, experts_l
        xf = x_l.reshape(-1, d)

        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_full)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, m.top_k)
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(idx[:, 0], m.n_experts, dtype=jnp.float32), axis=0)
        aux = m.n_experts * jnp.sum(me * ce)
        if dp is not None:
            aux = jax.lax.pmean(aux, dp)

        def chunk_body(x_c, idx_c, gates_c):
            tc = x_c.shape[0]
            e_flat = idx_c.reshape(-1)  # [tc*K]
            pos = _sort_positions(e_flat, e_pad)
            keep = (pos < cap).astype(x_c.dtype)
            pos_c = jnp.minimum(pos, cap - 1)
            src = jnp.repeat(x_c, m.top_k, axis=0) * keep[:, None]
            buf = jnp.zeros((e_pad, cap, d), x_c.dtype)
            buf = buf.at[e_flat, pos_c].add(src)
            # exchange: every shard keeps its e_loc experts' buffers
            recv = _a2a_maybe_quant(buf, ep, 0, 1, m.a2a_quant)  # [e_loc, ep*cap, d]
            h = jax.vmap(lambda ew, xe: mlp_apply(ew, xe, cfg.act))(
                experts_full, recv
            )
            back = _a2a_maybe_quant(h, ep, 1, 0, m.a2a_quant)  # [e_pad, cap, d]
            taken = back[e_flat, pos_c] * (keep * gates_c.reshape(-1).astype(x_c.dtype))[:, None]
            return jnp.sum(taken.reshape(tc, m.top_k, d), axis=1)

        body = jax.checkpoint(chunk_body)
        xs = xf.reshape(n_chunks, t_c, d)
        idxs = idx.reshape(n_chunks, t_c, m.top_k)
        gs = gates.reshape(n_chunks, t_c, m.top_k)
        _, ys = jax.lax.scan(
            lambda carry, args: (carry, body(*args)), None, (xs, idxs, gs)
        )
        return ys.reshape(x_l.shape), aux

    in_specs = (
        P(dp, None, None),
        P(fsdp if fsdp else None, None),
        jax.tree_util.tree_map(lambda _: P(ep, fsdp if fsdp else None), experts),
    )
    out_specs = (P(dp, None, None), P())
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    y, aux = fn(x, router_w, experts)
    return y, aux


def _moe_ep_capacity(params, x: Array, cfg: ModelConfig) -> Tuple[Array, Array]:
    """Capacity-based dispatch (sort-free, GShard-style) expressed with
    einsums so the SPMD partitioner shards experts over "ep" and inserts
    the token exchange (all-to-all / all-gather) automatically.

    x: [t, d] (t = local tokens; globally sharded over dp).
    dispatch [t, E, C] one-hot; expert inputs [E, C, d] = dispatchᵀ x;
    expert outs [E, C, d]; y = combine · outs.
    """
    m = cfg.moe
    t, d = x.shape
    gates, idx, aux = _route(params, x, m)

    capacity = _capacity(m, t, m.n_experts)
    # position of each (token, k) within its expert's buffer
    e_onehot = jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32)  # [t, K, E]
    # priority: earlier tokens first, k=0 before k=1 ...
    flat = e_onehot.reshape(t * m.top_k, m.n_experts)
    pos_in_expert = jnp.cumsum(flat, axis=0) - flat  # [t*K, E]
    pos = jnp.sum(pos_in_expert * flat, axis=-1).reshape(t, m.top_k).astype(jnp.int32)
    keep = pos < capacity
    gates = gates * keep.astype(gates.dtype)

    cap_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [t, K, C]
    dispatch = jnp.einsum("tke,tkc->tec", e_onehot, cap_onehot * keep[..., None])
    combine = jnp.einsum("tke,tkc,tk->tec", e_onehot, cap_onehot, gates)

    xin = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    xin = dist.constrain(xin.astype(x.dtype), "ep", None, None)

    def run(ep, xe):
        return mlp_apply(ep, xe, cfg.act)

    outs = jax.vmap(run)(params["experts"], xin)  # [E, C, d]
    outs = dist.constrain(outs, "ep", None, None)
    y = jnp.einsum("tec,ecd->td", combine, outs.astype(jnp.float32))
    return y.astype(x.dtype), aux
