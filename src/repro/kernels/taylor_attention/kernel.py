"""Pallas TPU kernel: chunked causal Taylor (order-2) linear attention.

Algorithm (one program per (batch·kv-head, d_v tile); sequential over chunk
index with VMEM-resident moment state):

  per chunk c:
    for each query-group head g:                      # GQA: G q-heads share state
      S   = (Q_g K_cᵀ)·a                               # C×C tile on the MXU
      P   = tril(1 + S + S²/2)                         # truncated-exp scores
      num = P V_c  +  s0  +  a·(Q_g S1)                # intra + inter moments
            + (a²/2)·Σ_t (Q_g ⊗ Q_g)_t S2_t            # D-tiled: no C×D×DV temp
      den = rowsum(P) + (c·C + i + 1) + a·(Q_g z1) + (a²/2)·(Q_g z2)·Q_g
      out = num / den
    S1 += K_cᵀV_c ; z1 += ΣK ; s0 += ΣV ; z2 += KᵀK
    S2_t += ((K ⊗ K_t) reshaped)ᵀ V_c                  # D-tiled outer product

Query groups run in a ``fori_loop``, so VMEM use and compile time do not
grow with G.  VMEM budget (f32 state): S2 = D²·DVt·4B — with D=128,
DVt=128 that is 8.4 MiB.  With the q⊗q tiles, double-buffered blocks and
the other moments, the v5e compiler reports a 21.4 MiB scoped allocation
for the forward and 17.0 MiB for the backward pair (qwen2-1.5b widths,
bf16): above Mosaic's 16 MiB default, so both set ``VMEM_LIMIT_BYTES``
(64 MiB, which also covers G=48 MQA; a v5e core has 128 MiB).  D must be
≤128 after padding (d=256 heads — gemma-7b — stay on the XLA chunked
path; see DESIGN.md §VMEM constraint).

Zero-padding contract (ops.py): padded key/value rows are all-zero, so every
moment contribution vanishes and the causal mask alone keeps the constant-1
term exact for real query rows.  Padded D columns contribute 0 to dots.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128
D_TILE = 32  # first-axis tile of the second moment (controls transient size)
# Scoped-VMEM limit of the forward and backward kernels (module docstring).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def scores(q, k, a, causal, order):
    """(s, p): scaled logits and causally-masked truncated-exp scores.

    Shared by the forward and backward kernels so the score function can
    never silently diverge between them."""
    f32 = jnp.float32
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=f32
    ) * a
    p = 1.0 + s
    if order >= 2:
        p = p + 0.5 * jnp.square(s)
    return s, jnp.where(causal, p, 0.0)


def dscores(dp, s, causal, a, order):
    """ds = causal(dp · d/ds[1 + s + s²/2]) · a — the VJP of ``scores``."""
    deriv = dp if order < 2 else dp * (1.0 + s)
    return jnp.where(causal, deriv, 0.0) * a


def outer_tile(x, t0):
    """Rows of the D-tiled outer product: ``[C, D] -> [C, D_TILE·D]`` with
    ``out[c, i·D + j] = x[c, t0 + i] · x[c, j]``.

    A static slice plus ``expand_dims``: fancy indexing (``x[:, a:b, None]``)
    traces to a gather, which Mosaic cannot lower."""
    c, d = x.shape
    xt = jax.lax.slice_in_dim(x, t0, t0 + D_TILE, axis=1)
    return (
        jnp.expand_dims(xt, 2) * jnp.expand_dims(x, 1)
    ).reshape(c, D_TILE * d)


def accumulate_state(
    k,  # [C, D]  f32
    v,  # [C, DVt] f32
    s0_ref,
    s1_ref,
    z1_ref,
    z2_ref,
    s2_ref,
    *,
    order: int,
    d: int,
):
    """Accumulate one chunk of keys/values into the VMEM moment state.

    Shared by the forward kernel and the backward dq kernel (which re-runs
    the same forward-direction chunk scan to rebuild S_{<c}).
    """
    f32 = jnp.float32
    C = k.shape[0]
    if s0_ref is not None:  # the bwd dq kernel has no numerator read: no S0
        s0_ref[0] = s0_ref[0] + jnp.sum(v, axis=0)
    z1_ref[0] = z1_ref[0] + jnp.sum(k, axis=0)
    s1_ref[...] = s1_ref[...] + jax.lax.dot_general(
        k, v, (((0,), (0,)), ((), ())), preferred_element_type=f32
    )
    if order >= 2:
        z2_ref[...] = z2_ref[...] + jax.lax.dot_general(
            k, k, (((0,), (0,)), ((), ())), preferred_element_type=f32
        )
        for t0 in range(0, d, D_TILE):
            kk = outer_tile(k, t0)  # [C, Dt*D]
            s2_ref[t0 * d : (t0 + D_TILE) * d, :] = s2_ref[
                t0 * d : (t0 + D_TILE) * d, :
            ] + jax.lax.dot_general(
                kk, v, (((0,), (0,)), ((), ())), preferred_element_type=f32
            )


def _taylor_fwd_kernel(
    q_ref,  # [1, G, C, D]
    k_ref,  # [1, C, D]
    v_ref,  # [1, C, DVt]
    out_ref,  # [1, G, C, DVt]
    s0_ref,  # [1, DVt]        VMEM scratch (f32)
    s1_ref,  # [D, DVt]
    z1_ref,  # [1, D]
    z2_ref,  # [D, D]
    s2_ref,  # [D*D, DVt]
    *,
    a: float,
    order: int,
    chunk: int,
    d: int,
):
    c_idx = pl.program_id(2)
    G = q_ref.shape[1]
    C = chunk
    D = d
    f32 = jnp.float32

    @pl.when(c_idx == 0)
    def _init():
        s0_ref[...] = jnp.zeros_like(s0_ref)
        s1_ref[...] = jnp.zeros_like(s1_ref)
        z1_ref[...] = jnp.zeros_like(z1_ref)
        z2_ref[...] = jnp.zeros_like(z2_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    k = k_ref[0].astype(f32)  # [C, D]
    v = v_ref[0].astype(f32)  # [C, DVt]

    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    causal = row >= col
    # constant-1 term of the denominator for all PREVIOUS chunks' keys
    # (rowsum(P) already counts the current chunk's 1s)
    count = (c_idx * C).astype(f32)

    half_a2 = 0.5 * a * a

    def group(g, carry):
        q = q_ref[0, g].astype(f32)  # [C, D]
        _, p = scores(q, k, a, causal, order)  # [C, C]

        num = jax.lax.dot(p, v, preferred_element_type=f32)  # [C, DVt]
        den = jnp.sum(p, axis=1) + count  # [C] (count is scalar-broadcast)

        # inter-chunk: first-order moments
        num = num + s0_ref[0][None, :]
        num = num + a * jax.lax.dot(q, s1_ref[...], preferred_element_type=f32)
        den = den + a * jnp.sum(q * z1_ref[0][None, :], axis=1)
        if order >= 2:
            # quadratic numerator, D-tiled: (q ⊗ q_t) @ S2_t
            acc = jnp.zeros_like(num)
            for t0 in range(0, D, D_TILE):
                qq = outer_tile(q, t0)  # [C, Dt*D]
                acc = acc + jax.lax.dot(
                    qq, s2_ref[t0 * D : (t0 + D_TILE) * D, :],
                    preferred_element_type=f32,
                )
            num = num + half_a2 * acc
            u = jax.lax.dot(q, z2_ref[...], preferred_element_type=f32)  # [C, D]
            den = den + half_a2 * jnp.sum(u * q, axis=1)

        den = jnp.where(jnp.abs(den) < 1e-6, 1e-6, den)
        out_ref[0, g] = (num / den[:, None]).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, G, group, 0)

    # ---- state update with this chunk's keys/values ----
    accumulate_state(
        k, v, s0_ref, s1_ref, z1_ref, z2_ref, s2_ref, order=order, d=D
    )


def taylor_fwd_pallas(
    q: jax.Array,  # [BK, G, N, D]  (pre-normalised, padded)
    k: jax.Array,  # [BK, N, D]
    v: jax.Array,  # [BK, N, DV]
    *,
    alpha: float,
    order: int = 2,
    chunk: int = DEFAULT_CHUNK,
    dv_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Raw Pallas forward: causal Taylor attention in kernel layout.

    Expects inputs already normalised and zero-padded by
    ``ops._kernel_layout`` (head dim ≤ 128 lanes, sequence a multiple of
    ``chunk``).  Use ``ops.taylor_attention_kernel`` unless you are doing
    kernel work.

    Args:
      q: grouped queries ``[b·hk, g, n, d]`` (g = h // hk query groups).
      k: keys ``[b·hk, n, d]``.
      v: values ``[b·hk, n, dv]``.
      alpha: logit scale (already padding-compensated by the wrapper).
      order: Taylor expansion order of exp, 1 or 2.
      chunk: chunk size of the grid's sequence axis (static).
      dv_tile: value-column tile per program (static; dv % dv_tile == 0).
      interpret: run under the Pallas interpreter (CPU/tests).

    Returns:
      Attention output ``[b·hk, g, n, dv]`` (f32), still padded.
    """
    bk, g, n, d = q.shape
    dv = v.shape[-1]
    assert n % chunk == 0, (n, chunk)
    assert dv % dv_tile == 0, (dv, dv_tile)
    assert d <= 128, f"kernel supports head dim ≤128 after padding, got {d}"
    a = 1.0 / (alpha * d**0.5)
    nc = n // chunk
    dvt = dv // dv_tile

    kernel = functools.partial(
        _taylor_fwd_kernel, a=a, order=order, chunk=chunk, d=d
    )
    grid = (bk, dvt, nc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, chunk, d), lambda b, t, c: (b, 0, c, 0)),
            pl.BlockSpec((1, chunk, d), lambda b, t, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv_tile), lambda b, t, c: (b, c, t)),
        ],
        out_specs=pl.BlockSpec((1, g, chunk, dv_tile), lambda b, t, c: (b, 0, c, t)),
        out_shape=jax.ShapeDtypeStruct((bk, g, n, dv), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, dv_tile), jnp.float32),
            pltpu.VMEM((d, dv_tile), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((d, d), jnp.float32),
            pltpu.VMEM((d * d, dv_tile), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(q, k, v)
