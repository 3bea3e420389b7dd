"""Pallas TPU decode kernel: one pass over a layer's second moment.

One decode step of order-2 Taylor attention adds ``k ⊗ k ⊗ v`` to each
layer's second moment ``S2 [b, hk, d, d, dv]`` (dense f32) and contracts
the new ``S2`` against ``q ⊗ q``.  ``S2`` is nearly all of a layer's
decode state (100.7 MB a layer at qwen2-1.5b's widths and six slots), so
the step is bound by the bytes it moves.  Done in XLA, the update and the
readout cannot share one fusion (the readout is a reduction), and the
layer's slice of the stacked state crosses HBM several times.  This kernel
reads each tile of the slice once, writes it once, and reads the new
tile out while it is still in VMEM:

  per (row b, kv head h, tile of T first-axis rows of S2):
    new  = old + (k_i k_j) v               # i in the tile, exact f32 VPU
    out  = new if keep[b] else old         # a row that does not advance
                                           # comes back bit for bit
    num2 += (q ⊗ q)_tile · new             # MXU: M = g, K = T·d, N = dv

It takes the WHOLE stacked leaf ``[n_groups, run_len, b, hk, d, d, dv]``,
aliased input to output, and picks layer ``(g, r)`` in its ``index_map``
from scalar-prefetched indices: a slice taken outside the kernel would be
materialised by XLA, and writing it back would be another copy.

VMEM: a 32-row tile is ``32·d·dv·4`` bytes (2 MiB at d = dv = 128); in and
out, double-buffered, plus the new tile, stay under 12 MiB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.taylor_attention.kernel import D_TILE, VMEM_LIMIT_BYTES

Array = jax.Array


def _decode_s2_kernel(
    layer_ref,  # SMEM [2] int32: (group, run position) of the layer
    keep_ref,  # SMEM [b] int32: 1 where the row advances
    s2_ref,  # [T, d, dv] tile of the layer's S2 (aliased with out_ref)
    kt_ref,  # [T, 1, 1] the tile's rows of k
    k_ref,  # [d, 1] k as a column
    v_ref,  # [1, dv]
    qt_ref,  # [g, T] the tile's columns of the grouped queries
    q_ref,  # [g, d]
    out_ref,  # [T, d, dv]
    num_ref,  # [g, dv] f32, accumulated over the tile axis
):
    del layer_ref  # used by the index maps only
    t_idx = pl.program_id(2)
    f32 = jnp.float32
    t, d, dv = s2_ref.shape

    old = s2_ref[...]
    kv = k_ref[...] * v_ref[...]  # [d, dv]: k_j v
    new = old + kt_ref[...] * kv[None]  # + k_i k_j v
    out_ref[...] = jnp.where(keep_ref[pl.program_id(0)] != 0, new, old)

    q = q_ref[...].astype(f32)
    qt = qt_ref[...].astype(f32)
    g = q.shape[0]
    qq = (qt[:, :, None] * q[:, None, :]).reshape(g, t * d)  # (q_i q_j)
    part = jax.lax.dot(qq, new.reshape(t * d, dv), preferred_element_type=f32)

    @pl.when(t_idx == 0)
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)

    num_ref[...] += part


def taylor_decode_s2(
    s2: Array,
    layer: Array,
    keep: Array,
    q: Array,
    k: Array,
    v: Array,
    *,
    interpret: bool = False,
):
    """Advance layer ``layer`` of a stacked second moment by one token and
    read it out, in one pass over that layer's slice.

    Args:
      s2: the whole stacked leaf ``[n_groups, run_len, b, hk, d, d, dv]``
        f32, updated in place (the caller must not use it afterwards).
      layer: ``[2]`` int32, the ``(group, run position)`` to advance.
      keep: ``[b]`` bool; rows where it is False come back bit for bit.
      q: grouped queries ``[b, hk, g, d]`` (normalised).
      k: the step's keys ``[b, hk, d]`` (normalised).
      v: the step's values ``[b, hk, dv]``.
      interpret: run under the Pallas interpreter (CPU/tests).

    Returns:
      ``(num2 [b, hk, g, dv] f32, s2)``: ``num2`` is ``(q ⊗ q) · S2_new``
      (unscaled; every row, kept or not), ``s2`` the updated stack.
    """
    f32 = jnp.float32
    b, hk, g, d = q.shape
    dv = v.shape[-1]
    t = D_TILE if d % D_TILE == 0 else d
    nt = d // t
    k = k.astype(f32)
    v = v.astype(f32)
    # the tile's columns of q as their own operand: no lane slice at a
    # traced offset inside the kernel
    qt = q.reshape(b, hk, g, nt, t).transpose(0, 1, 3, 2, 4)

    def tile_of_s2(bi, hi, ti, layer_ref, keep_ref):
        return (layer_ref[0], layer_ref[1], bi, hi, ti, 0, 0)

    def per_tile(bi, hi, ti, *_):  # [b, hk, tiles, ...] operands
        return (bi, hi, ti, 0, 0)

    def per_head(bi, hi, ti, *_):  # the same block for every tile
        return (bi, hi, 0, 0)

    s2_spec = pl.BlockSpec((None,) * 4 + (t, d, dv), tile_of_s2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hk, nt),
        in_specs=[
            s2_spec,
            pl.BlockSpec((None, None, t, 1, 1), per_tile),
            pl.BlockSpec((None, None, d, 1), per_head),
            pl.BlockSpec((None, None, 1, dv), per_head),
            pl.BlockSpec((None, None, None, g, t), per_tile),
            pl.BlockSpec((None, None, g, d), per_head),
        ],
        out_specs=[s2_spec, pl.BlockSpec((None, None, g, dv), per_head)],
    )
    s2, num2 = pl.pallas_call(
        _decode_s2_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(s2.shape, s2.dtype),
            jax.ShapeDtypeStruct((b, hk, g, dv), f32),
        ],
        # operand 2 (after the two prefetched scalars) is the stack
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="taylor_decode_s2",
    )(
        layer.astype(jnp.int32),
        keep.astype(jnp.int32),
        s2,
        k.reshape(b, hk, d, 1, 1),
        k.reshape(b, hk, d, 1),
        v.reshape(b, hk, 1, dv),
        qt,
        q,
    )
    return num2, s2
