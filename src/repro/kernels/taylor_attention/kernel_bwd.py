"""Pallas TPU backward kernels: chunked causal Taylor linear attention.

FlashLinearAttention-style two-pass recompute (the exact math of
core/taylor_vjp.py, re-expressed as two Pallas kernels so training never
leaves the accelerator):

  * **dq kernel** — re-runs the forward-direction chunk scan with the same
    VMEM-resident moment state as ``_taylor_fwd_kernel`` (S1/z1/z2/S2,
    D-tiled second moment; S0 is not needed because the numerator is never
    recomputed).  Per chunk it recomputes den, forms dnum = dout/den and
    dden = -Σ_v dout·out/den from the saved forward output, and emits dq
    plus the (den, dden) rows the reverse kernel needs.
  * **dk/dv kernel** — scans chunks in REVERSE (grid index maps flip the
    chunk index) carrying the accumulated future state-gradients
    (dS0/dS1/dz1/dz2/dS2) in VMEM scratch, and emits dk, dv.

The two calls are named ``taylor_bwd_dq`` and ``taylor_bwd_dkv``: the
compiled program, and so a profiler trace, shows each under its own name
rather than the enclosing remat's (``checkpoint``).  Neither contains the
forward's ``taylor_attention_kernel``.

Compute: ≈2× the forward (the standard recompute trade — see
DESIGN.md §Backward).  Residual HBM: q, k, v, dout plus the [*, G, N]
den/dden rows; no per-chunk state is ever materialised off-chip.

Zero-padding contract (shared with the forward via ops.py::_kernel_layout):
padded K/V rows are all-zero and padded dout rows are all-zero, so every
state-gradient contribution of a padded row vanishes and padded dq/dk/dv
rows come out exactly zero (they are sliced off anyway).  Padded D columns
contribute 0 to every dot product.

VMEM budget mirrors the forward: the D-tiled second moment (or its
gradient) dominates at D²·DVt·4B = 8.4 MiB for D = DVt = 128; the pair
runs under the forward's ``VMEM_LIMIT_BYTES`` (kernel.py docstring).
Query groups run in a ``fori_loop``, as in the forward.  D ≤ 128 and
DV ≤ 128 after padding; larger heads stay on the XLA taylor_vjp path
(ops.py dispatch).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.taylor_attention.kernel import (
    D_TILE,
    DEFAULT_CHUNK,
    VMEM_LIMIT_BYTES,
    accumulate_state,
    dscores,
    outer_tile,
    scores,
)

DEN_EPS = 1e-6  # matches the forward kernel's denominator clamp


def _taylor_bwd_dq_kernel(
    q_ref,  # [1, G, C, D]
    k_ref,  # [1, C, D]
    v_ref,  # [1, C, DV]
    do_ref,  # [1, G, C, DV]
    o_ref,  # [1, G, C, DV]   forward output (saved residual)
    dq_ref,  # [1, G, C, D]   out
    den_ref,  # [1, G, C]     out (clamped denominator, f32)
    dden_ref,  # [1, G, C]    out (denominator cotangent, f32)
    s1_ref,  # [D, DV]        VMEM scratch (f32): forward moment state
    z1_ref,  # [1, D]
    z2_ref,  # [D, D]
    s2_ref,  # [D*D, DV]
    *,
    a: float,
    order: int,
    chunk: int,
    d: int,
):
    """Forward-direction rescan emitting dq.

    The numerator is NOT recomputed: ``dden = -Σ_v dout·out / den`` uses the
    saved forward output (the flash-attention residual trick), so the only
    state reads are the ones dq itself needs (S1/z1/z2/S2) plus the cheap
    denominator terms.  This is what keeps the whole backward within the
    ~2.3× forward-FLOP recompute budget (see bench_kernel.py).
    """
    c_idx = pl.program_id(1)
    G = q_ref.shape[1]
    C = chunk
    D = d
    f32 = jnp.float32

    @pl.when(c_idx == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        z1_ref[...] = jnp.zeros_like(z1_ref)
        z2_ref[...] = jnp.zeros_like(z2_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    k = k_ref[0].astype(f32)  # [C, D]
    v = v_ref[0].astype(f32)  # [C, DV]

    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    causal = row >= col
    count = (c_idx * C).astype(f32)
    half_a2 = 0.5 * a * a

    def group(g, carry):
        q = q_ref[0, g].astype(f32)  # [C, D]
        do = do_ref[0, g].astype(f32)  # [C, DV]
        o = o_ref[0, g].astype(f32)  # [C, DV]
        s, p = scores(q, k, a, causal, order)

        # ---- recompute den exactly as the forward kernel ----
        den = jnp.sum(p, axis=1) + count
        den = den + a * jnp.sum(q * z1_ref[0][None, :], axis=1)
        u = None
        if order >= 2:
            u = jax.lax.dot(q, z2_ref[...], preferred_element_type=f32)  # [C, D]
            den = den + half_a2 * jnp.sum(u * q, axis=1)
        den = jnp.where(jnp.abs(den) < DEN_EPS, DEN_EPS, den)

        # ---- cotangents of (num, den) via the saved output ----
        dnum = do / den[:, None]  # [C, DV]
        dden = -jnp.sum(do * o, axis=1) / den  # [C]

        # ---- intra-chunk dq ----
        dp = jax.lax.dot_general(
            dnum, v, (((1,), (1,)), ((), ())), preferred_element_type=f32
        ) + dden[:, None]  # [C, C]
        ds = dscores(dp, s, causal, a, order)
        dq = jax.lax.dot(ds, k, preferred_element_type=f32)  # [C, D]

        # ---- inter-chunk dq (state S_{<c} is a constant here) ----
        dq = dq + a * jax.lax.dot_general(
            dnum, s1_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=f32,
        )
        dq = dq + a * dden[:, None] * z1_ref[0][None, :]
        if order >= 2:
            # d/dq of half_a2·(q⊗q)·S2 = 2·half_a2·Σ_{e,v} q_e S2[·,e,v] dnum_v
            parts = []
            for t0 in range(0, D, D_TILE):
                w = jax.lax.dot_general(
                    dnum, s2_ref[t0 * D : (t0 + D_TILE) * D, :],
                    (((1,), (1,)), ((), ())), preferred_element_type=f32,
                )  # [C, Dt*D]
                w3 = w.reshape(C, D_TILE, D)
                parts.append(jnp.sum(w3 * q[:, None, :], axis=2))  # [C, Dt]
            dq = dq + (2.0 * half_a2) * jnp.concatenate(parts, axis=1)
            dq = dq + (2.0 * half_a2) * dden[:, None] * u

        dq_ref[0, g] = dq.astype(dq_ref.dtype)
        den_ref[0, g] = den
        dden_ref[0, g] = dden
        return carry

    jax.lax.fori_loop(0, G, group, 0)

    accumulate_state(
        k, v, None, s1_ref, z1_ref, z2_ref, s2_ref, order=order, d=D
    )


def _taylor_bwd_dkv_kernel(
    q_ref,  # [1, G, C, D]
    k_ref,  # [1, C, D]
    v_ref,  # [1, C, DV]
    do_ref,  # [1, G, C, DV]
    den_ref,  # [1, G, C]
    dden_ref,  # [1, G, C]
    dk_ref,  # [1, C, D]    out
    dv_ref,  # [1, C, DV]   out
    ds0_ref,  # [1, DV]     VMEM scratch (f32): future state-gradients
    ds1_ref,  # [D, DV]
    dz1_ref,  # [1, D]
    dz2_ref,  # [D, D]
    ds2_ref,  # [D*D, DV]
    *,
    a: float,
    order: int,
    chunk: int,
    d: int,
):
    """Reverse-scan program: grid index maps flip the chunk index, so
    program 0 sees the LAST chunk and the dstate scratch carries the
    gradient flowing from future chunks back to this chunk's keys/values."""
    c_idx = pl.program_id(1)
    G = q_ref.shape[1]
    C = chunk
    D = d
    f32 = jnp.float32

    @pl.when(c_idx == 0)
    def _init():
        ds0_ref[...] = jnp.zeros_like(ds0_ref)
        ds1_ref[...] = jnp.zeros_like(ds1_ref)
        dz1_ref[...] = jnp.zeros_like(dz1_ref)
        dz2_ref[...] = jnp.zeros_like(dz2_ref)
        ds2_ref[...] = jnp.zeros_like(ds2_ref)

    k = k_ref[0].astype(f32)  # [C, D]
    v = v_ref[0].astype(f32)  # [C, DV]

    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    causal = row >= col
    half_a2 = 0.5 * a * a

    # ---- contribution of FUTURE chunks (the carried dstate), read before
    # this chunk's own accumulation below.  The forward updated the state
    # AFTER the read, so a chunk's k/v only feed future queries. ----
    dv = ds0_ref[0][None, :] + jax.lax.dot(
        k, ds1_ref[...], preferred_element_type=f32
    )  # [C, DV]
    dk = dz1_ref[0][None, :] + jax.lax.dot_general(
        v, ds1_ref[...], (((1,), (1,)), ((), ())), preferred_element_type=f32
    )  # [C, D]
    if order >= 2:
        dk = dk + 2.0 * jax.lax.dot(k, dz2_ref[...], preferred_element_type=f32)
        parts = []
        for t0 in range(0, D, D_TILE):
            block = ds2_ref[t0 * D : (t0 + D_TILE) * D, :]  # [Dt*D, DV]
            # dk[j, t] += 2·Σ_{e,v} k[j,e]·dS2[t,e,v]·v[j,v]   (S2 = k⊗k⊗v)
            w = jax.lax.dot_general(
                v, block, (((1,), (1,)), ((), ())), preferred_element_type=f32
            )  # [C, Dt*D]
            w3 = w.reshape(C, D_TILE, D)
            parts.append(2.0 * jnp.sum(w3 * k[:, None, :], axis=2))  # [C, Dt]
            # dv[j, v] += Σ_{t,e} k[j,t]·k[j,e]·dS2[t,e,v]
            kk = outer_tile(k, t0)
            dv = dv + jax.lax.dot(kk, block, preferred_element_type=f32)
        dk = dk + jnp.concatenate(parts, axis=1)

    def group(g, carry):
        dk, dv = carry
        q = q_ref[0, g].astype(f32)  # [C, D]
        do = do_ref[0, g].astype(f32)  # [C, DV]
        den = den_ref[0, g]  # [C] (already clamped by the dq kernel)
        dden = dden_ref[0, g]  # [C]
        dnum = do / den[:, None]  # [C, DV]

        # ---- intra-chunk dk/dv ----
        s, p = scores(q, k, a, causal, order)
        dp = jax.lax.dot_general(
            dnum, v, (((1,), (1,)), ((), ())), preferred_element_type=f32
        ) + dden[:, None]
        ds = dscores(dp, s, causal, a, order)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=f32
        )
        dv = dv + jax.lax.dot_general(
            p, dnum, (((0,), (0,)), ((), ())), preferred_element_type=f32
        )

        # ---- accumulate THIS chunk's contribution to the state gradient
        # (its inter-chunk read used S_{<c}: flows to EARLIER chunks) ----
        ds0_ref[0] = ds0_ref[0] + jnp.sum(dnum, axis=0)
        dz1_ref[0] = dz1_ref[0] + a * jnp.sum(dden[:, None] * q, axis=0)
        ds1_ref[...] = ds1_ref[...] + a * jax.lax.dot_general(
            q, dnum, (((0,), (0,)), ((), ())), preferred_element_type=f32
        )
        if order >= 2:
            dz2_ref[...] = dz2_ref[...] + half_a2 * jax.lax.dot_general(
                dden[:, None] * q, q, (((0,), (0,)), ((), ())),
                preferred_element_type=f32,
            )
            for t0 in range(0, D, D_TILE):
                qq = outer_tile(q, t0)
                ds2_ref[t0 * D : (t0 + D_TILE) * D, :] = ds2_ref[
                    t0 * D : (t0 + D_TILE) * D, :
                ] + half_a2 * jax.lax.dot_general(
                    qq, dnum, (((0,), (0,)), ((), ())),
                    preferred_element_type=f32,
                )
        return dk, dv

    dk, dv = jax.lax.fori_loop(0, G, group, (dk, dv))

    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def taylor_bwd_pallas(
    q: jax.Array,  # [BK, G, N, D]   (pre-normalised, padded)
    k: jax.Array,  # [BK, N, D]
    v: jax.Array,  # [BK, N, DV]
    dout: jax.Array,  # [BK, G, N, DV]  (zero-padded like v)
    out: jax.Array,  # [BK, G, N, DV]  forward output (saved residual)
    *,
    alpha: float,
    order: int = 2,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(dq, dk, dv) of the Pallas Taylor forward, via the two-kernel pair.

    Unlike the forward there is no d_v tiling: dden couples all value
    columns, so DV must fit one 128-lane tile (ops.py falls back to the
    XLA path otherwise).

    Args:
      q: queries ``[BK, G, N, D]`` (pre-normalised, padded — the
        ``ops._kernel_layout`` contract).
      k: keys ``[BK, N, D]``.
      v: values ``[BK, N, DV]``.
      dout: output cotangent ``[BK, G, N, DV]`` (zero-padded like v).
      out: the SAVED forward output ``[BK, G, N, DV]`` — pass 1 derives
        the denominator cotangent from it (flash-attention residual
        trick) instead of recomputing the numerator.
      alpha: logit down-scale (must match the forward launch).
      order: Taylor order (1 or 2).
      chunk: sequence chunk of the scan (must divide N).
      interpret: run under the Pallas interpreter (CPU/tests).

    Returns:
      ``(dq [BK, G, N, D], dk [BK, N, D], dv [BK, N, DV])`` f32.
    """
    bk, g, n, d = q.shape
    dv = v.shape[-1]
    assert n % chunk == 0, (n, chunk)
    assert d <= 128, f"backward kernel needs head dim ≤128, got {d}"
    assert dv <= 128, f"backward kernel needs value dim ≤128, got {dv}"
    a = 1.0 / (alpha * d**0.5)
    nc = n // chunk

    moment_scratch = [
        pltpu.VMEM((d, dv), jnp.float32),   # S1 / dS1
        pltpu.VMEM((1, d), jnp.float32),    # z1 / dz1
        pltpu.VMEM((d, d), jnp.float32),    # z2 / dz2
        pltpu.VMEM((d * d, dv), jnp.float32),  # S2 / dS2 (D-tiled rows)
    ]
    common = dict(a=a, order=order, chunk=chunk, d=d)

    # ---- pass 1 (forward direction): dq, den, dden ----
    dq, den, dden = pl.pallas_call(
        functools.partial(_taylor_bwd_dq_kernel, **common),
        grid=(bk, nc),
        in_specs=[
            pl.BlockSpec((1, g, chunk, d), lambda b, c: (b, 0, c, 0)),
            pl.BlockSpec((1, chunk, d), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, g, chunk, dv), lambda b, c: (b, 0, c, 0)),
            pl.BlockSpec((1, g, chunk, dv), lambda b, c: (b, 0, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, g, chunk, d), lambda b, c: (b, 0, c, 0)),
            pl.BlockSpec((1, g, chunk), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, g, chunk), lambda b, c: (b, 0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bk, g, n, d), jnp.float32),
            jax.ShapeDtypeStruct((bk, g, n), jnp.float32),
            jax.ShapeDtypeStruct((bk, g, n), jnp.float32),
        ],
        scratch_shapes=moment_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="taylor_bwd_dq",
    )(q, k, v, dout, out)

    # ---- pass 2 (reverse direction): dk, dv ----
    rev = lambda c: nc - 1 - c
    dk, dvv = pl.pallas_call(
        functools.partial(_taylor_bwd_dkv_kernel, **common),
        grid=(bk, nc),
        in_specs=[
            pl.BlockSpec((1, g, chunk, d), lambda b, c: (b, 0, rev(c), 0)),
            pl.BlockSpec((1, chunk, d), lambda b, c: (b, rev(c), 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, rev(c), 0)),
            pl.BlockSpec((1, g, chunk, dv), lambda b, c: (b, 0, rev(c), 0)),
            pl.BlockSpec((1, g, chunk), lambda b, c: (b, 0, rev(c))),
            pl.BlockSpec((1, g, chunk), lambda b, c: (b, 0, rev(c))),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d), lambda b, c: (b, rev(c), 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, rev(c), 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bk, n, d), jnp.float32),
            jax.ShapeDtypeStruct((bk, n, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, dv), jnp.float32)] + moment_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="taylor_bwd_dkv",
    )(q, k, v, dout, den, dden)

    return dq, dk, dvv
