"""Context parallelism for SSD (Mamba2) — the decay-weighted analogue of
core/context_parallel.py.

SSD states are *decayed* sums, so merging sequence shards needs one extra
ingredient vs the Taylor moments: each shard's incoming state is

    H_i = Σ_{j<i} exp(Σ_{j<l<i} total_l) · L_j

where L_j is shard j's locally-accumulated state and total_j its total log
decay.  One all_gather of (L_j [b,H,P,N], total_j [b,H]) replaces any O(n)
ring exchange; outputs are corrected in closed form with the local
cumulative decays (y_t += C_t · exp(cum_t) H_i).  Exact (tested against the
unsharded chunked scan).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.ssm import _ssd_chunked

Array = jax.Array


def ssd_context_parallel(
    x: Array,  # [b, n, H, Pd]
    dt: Array,  # [b, n, H] (post-softplus)
    A: Array,  # [H] (negative)
    B: Array,  # [b, n, G, N]
    C: Array,  # [b, n, G, N]
    mesh: Mesh,
    axis: str,
    chunk: int = 128,
    dp_axis=None,
) -> Array:
    b, n, H, Pd = x.shape
    n_shards = mesh.shape[axis]
    assert n % (n_shards * chunk) == 0, (n, n_shards, chunk)
    if dp_axis is not None:
        size = 1
        for a_ in (dp_axis if isinstance(dp_axis, tuple) else (dp_axis,)):
            size *= mesh.shape[a_]
        if b % size != 0:
            dp_axis = None

    def local(x_l, dt_l, B_l, C_l):
        bl, n_loc = x_l.shape[0], x_l.shape[1]
        y_local, L = _ssd_chunked(x_l, dt_l, A, B_l, C_l, chunk, return_state=True)
        la = dt_l.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]
        total = jnp.sum(la, axis=1)  # [b, H]

        idx = jax.lax.axis_index(axis)
        Ls = jax.lax.all_gather(L, axis)  # [S, b, H, P, N]
        totals = jax.lax.all_gather(total, axis)  # [S, b, H]
        tcum = jnp.cumsum(totals, axis=0)  # inclusive prefix of log decays
        # w_j = exp(Σ_{l=j+1..i-1} total_l) for j < i, else 0
        jrange = jnp.arange(n_shards)
        prev = jnp.where(idx > 0, tcum[jnp.maximum(idx - 1, 0)], jnp.zeros_like(tcum[0]))
        logw = prev[None] - tcum  # [S, b, H]: Tcum_{i-1} - Tcum_j
        w = jnp.where((jrange < idx)[:, None, None], jnp.exp(logw), 0.0)
        H_in = jnp.einsum("sbh,sbhpn->bhpn", w, Ls)

        # output correction: y_t += C_t · exp(cum_t) H_in
        rep = H // B_l.shape[2]
        Ch = jnp.repeat(C_l, rep, axis=2).astype(jnp.float32)  # [b, n, H, N]
        cum = jnp.cumsum(la, axis=1)  # [b, n, H]
        y_corr = jnp.einsum("bihn,bhpn,bih->bihp", Ch, H_in, jnp.exp(cum))
        return y_local + y_corr

    spec4 = P(dp_axis, axis, None, None)
    spec3 = P(dp_axis, axis, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec4, spec3, spec4, spec4),
        out_specs=spec4,
        check_vma=False,
    )
    return fn(x, dt, B, C)
