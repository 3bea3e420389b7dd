"""The paper's order-2 Taylor linear-attention backend.

Two impls, selected by ``ModelConfig.attn_impl``:

  * ``"xla"``    — the chunked scan of ``core/taylor.py`` (custom-VJP
    training path, context parallelism, every TaylorConfig variant).
  * ``"pallas"`` — the fused TPU kernel pair of
    ``kernels/taylor_attention`` (forward AND two-pass backward) through
    ``taylor_attention_kernel_trainable``; runs under the Pallas
    interpreter off-TPU.  Causal self-attention only, d ≤ 128 after
    padding, full second moment, standard (+1) expansion — the registry
    rejects configs outside this envelope when "pallas" is forced.

``"auto"`` picks the kernel exactly when it wins: on TPU, inside the
envelope; everywhere else the XLA scan (off-TPU the interpreter is a
correctness tool, not an execution engine).  Prefill and decode always
run the XLA moment-state paths — prefill needs the chunk-scan's
``return_state`` handoff and decode is state-bound, not compute-bound.

Decode/cross state is the O(1) ``TaylorState`` (running moments); states
of consecutive sequence shards merge by addition, which is what makes the
single-exchange context parallelism of ``core/context_parallel.py`` work.
"""

from __future__ import annotations

import dataclasses

import jax
from jax.sharding import PartitionSpec as P

from repro.backends.base import AttentionBackend
from repro.core import (
    init_taylor_state,
    merge_states,
    taylor_attention,
    taylor_attention_chunked,
    taylor_attention_noncausal,
    taylor_decode_step,
    taylor_prefill_state,
    taylor_state_read,
)
from repro.kernels.taylor_attention.ops import taylor_attention_kernel_trainable

Array = jax.Array

# The Pallas kernels' envelope: head dim ≤ 128 lanes after padding (the
# second-moment VMEM budget — see kernels/taylor_attention/kernel.py).
_PALLAS_MAX_HEAD_DIM = 128


def _pallas_fits(cfg) -> bool:
    """One envelope for both "auto" selection and forced-"pallas"
    validation — the two must never disagree about a config."""
    t = cfg.taylor
    return (
        not t.minus_one
        and not t.sym_state
        and t.decay == 1.0
        and cfg.resolved_head_dim <= _PALLAS_MAX_HEAD_DIM
        and cfg.attn_sharding != "cp"
        and not AttentionBackend._uses_cross(cfg)
    )


class TaylorBackend(AttentionBackend):
    """Order-1/2 Taylor linear attention (XLA chunked scan + Pallas kernels)."""

    name = "taylor"
    state_kind = "moments"
    supports_cross = True
    supports_cp = True
    impls = ("xla", "pallas")
    # The O(1) moment state (S1/S2 dominate per-slot bytes) may be held
    # int8/fp8-quantised between serve dispatches, with per-head per-leaf
    # pow2 scales; absorb/read always run fp32 (serve/state_repr.py).
    state_dtypes = ("dense", "int8", "fp8")

    def validate(self, cfg):
        super().validate(cfg)
        t = cfg.taylor
        if t.decay != 1.0:
            if cfg.attn_sharding == "cp":
                raise ValueError(
                    "taylor decay is incompatible with context parallelism: "
                    "shard-state merge is addition, which a decayed state "
                    "violates (shard b must discount shard a by γ^len)"
                )
            if self._uses_cross(cfg):
                raise ValueError(
                    "taylor decay is causal-self-attention only, but the "
                    "model has cross/encoder blocks (a position-decayed "
                    "global source state is ill-defined)"
                )
            if cfg.attn_impl == "pallas":
                raise ValueError(
                    "attn_impl='pallas': the Pallas kernels implement the "
                    "undecayed recurrence; decay != 1.0 needs "
                    "attn_impl='xla' (or 'auto')"
                )
        if cfg.attn_impl != "pallas":
            return
        if t.minus_one:
            raise ValueError(
                "attn_impl='pallas': the Pallas kernels hardcode the "
                "standard (+1) expansion; the minus_one variant needs "
                "attn_impl='xla'"
            )
        if t.sym_state:
            raise ValueError(
                "attn_impl='pallas': the Pallas kernels use the full "
                "second moment; sym_state is an XLA/decode-memory "
                "optimisation — use attn_impl='xla' (or 'auto')"
            )
        if cfg.resolved_head_dim > _PALLAS_MAX_HEAD_DIM:
            raise ValueError(
                f"attn_impl='pallas': head_dim {cfg.resolved_head_dim} > "
                f"{_PALLAS_MAX_HEAD_DIM} exceeds the kernel's VMEM envelope "
                "(use attn_impl='xla'; see DESIGN.md §VMEM constraint)"
            )
        if cfg.attn_sharding == "cp":
            raise ValueError(
                "attn_impl='pallas': context parallelism runs the XLA "
                "chunked scan (the kernel has no state handoff); use "
                "attn_impl='auto' or 'xla' with attn_sharding='cp'"
            )
        if self._uses_cross(cfg):
            raise ValueError(
                "attn_impl='pallas': the kernel is causal-self-attention "
                "only, but the model has cross blocks — use "
                "attn_impl='auto' or 'xla'"
            )

    def resolve_impl(self, cfg) -> str:
        if cfg.attn_impl != "auto":
            return cfg.attn_impl
        if jax.default_backend() == "tpu" and _pallas_fits(cfg):
            return "pallas"
        return "xla"

    def draft_config(self, cfg):
        """Order-1 same-weights self-draft (the paper's order hierarchy).

        Drops the second-moment terms from the feature map — the draft
        state is ``(n0, s0, z1, s1)`` only, a large per-slot memory and
        FLOP cut — while reusing the target's weights verbatim (the
        Taylor feature map is parameter-free).  ``None`` when the target
        is already order 1 (no cheaper order below it).

        Args:
          cfg: the target model config.

        Returns:
          ``cfg`` with ``taylor.order = 1`` (``attn_impl`` forced to
          "xla": decode/prefill drive the XLA moment paths), or ``None``.
          Also ``None`` for hybrid schedules — the order hierarchy only
          applies to the taylor layers, and a draft that degrades some
          layers but not others has no cheaper-state story (serve falls
          back to the n-gram proposer).
        """
        if cfg.taylor.order < 2 or cfg.attention_schedule:
            return None
        return cfg.replace(
            taylor=dataclasses.replace(cfg.taylor, order=1), attn_impl="xla"
        )

    # -- protocol ------------------------------------------------------------

    def init_cache(self, cfg, batch, n_max, dtype):
        hk, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        return init_taylor_state(batch, hk, hd, hd, cfg.taylor)

    def apply(self, q, k, v, cfg, *, causal=True):
        if not causal:
            return taylor_attention_noncausal(q, k, v, cfg.taylor)
        if self.resolve_impl(cfg) == "pallas":
            return self._apply_kernel(q, k, v, cfg)
        if cfg.attn_sharding == "cp":
            o = self._maybe_cp(q, k, v, cfg)
            if o is not None:
                return o
        return taylor_attention(q, k, v, cfg.taylor, causal=True, chunk=cfg.attn_chunk)

    def _apply_kernel(self, q, k, v, cfg):
        """The Pallas kernel pair, once per shard under a sharding context.

        The SPMD partitioner cannot split a Mosaic kernel, so on a mesh the
        call runs inside ``shard_map``: batch over "dp" and heads over "tp"
        where they divide (GQA groups stay whole: q head i reads kv head
        i // g, and contiguous head blocks keep that pairing per shard);
        the sequence is never split — the causal scan needs all of it.
        """
        from repro.distributed import api as dist  # noqa: PLC0415 (cycle)

        def kernel(q, k, v):
            return taylor_attention_kernel_trainable(
                q, k, v, cfg.taylor, chunk=cfg.attn_chunk,
                interpret=jax.default_backend() != "tpu", backward="auto",
            )

        ctx = dist.active()
        if ctx is None:
            return kernel(q, k, v)
        mesh, rules = ctx
        dp, tp = rules.get("dp"), rules.get("tp")
        if q.shape[0] % dist.mesh_axis_size(mesh, dp):
            dp = None
        if (q.shape[1] % dist.mesh_axis_size(mesh, tp)
                or k.shape[1] % dist.mesh_axis_size(mesh, tp)):
            tp = None
        spec = P(dp, tp, None, None)
        return jax.shard_map(
            kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    def prefill(self, q, k, v, cfg, n_max):
        n = q.shape[2]
        if n % cfg.attn_chunk == 0 and n > cfg.attn_chunk:
            return taylor_attention_chunked(
                q, k, v, cfg.taylor, chunk=cfg.attn_chunk, return_state=True
            )
        o = taylor_attention(q, k, v, cfg.taylor, causal=True)
        return o, taylor_prefill_state(k, v, cfg.taylor)

    def decode_step(self, cache, q, k, v, cfg, pos):
        o, cache = taylor_decode_step(cache, q, k, v, cfg.taylor)
        return o, cache

    def prefill_chunk(self, cache, q, k, v, cfg, pos):
        """Chunk-scan continuation: one quadratic intra-chunk tile plus the
        inter-chunk read of the carried moment state (``initial_state``) —
        the MXU-friendly form of advancing the decode state by a whole
        chunk of prompt tokens (vs the base class's token-by-token scan).

        Args:
          cache: ``TaylorState`` to continue from.
          q: chunk queries ``[b, h, c, d]``.
          k: chunk keys ``[b, hk, c, d]``.
          v: chunk values ``[b, hk, c, dv]``.
          cfg: model config.
          pos: ``[b, c]`` positions (unused — the moment state is
            position-free; RoPE is applied by the model layer).

        Returns:
          ``(out [b, h, c, dv], new TaylorState)`` with all ``c`` tokens
          absorbed.
        """
        del pos
        return taylor_attention_chunked(
            q, k, v, cfg.taylor, chunk=q.shape[2],
            initial_state=cache, return_state=True,
        )

    def cache_pspec(self, cfg):
        """Logical axes of the ``TaylorState`` moment tensors: slots over
        "dp", kv heads over "tp"; when the kv-head dim cannot shard (MQA,
        or heads not divisible by the axis) the resolver's last-dim
        fallback puts "tp" on d_v for the s0/s1/s2 value moments instead.

        Args:
          cfg: model config (``order``/``sym_state`` decide which moment
            leaves exist and their shapes).

        Returns:
          ``TaylorState`` of logical ``PartitionSpec`` leaves congruent to
          ``init_cache``'s output.
        """
        from repro.core import TaylorState  # noqa: PLC0415

        t = cfg.taylor
        second = t.order >= 2
        # sym_state packs z2/s2 to [b, k, D2(, v)]; same leading axes.
        z2 = P("dp", "tp", None) if t.sym_state else P("dp", "tp", None, None)
        s2 = (
            P("dp", "tp", None, None)
            if t.sym_state
            else P("dp", "tp", None, None, None)
        )
        return TaylorState(
            n0=P("dp", "tp"),
            s0=P("dp", "tp", None),
            z1=P("dp", "tp", None),
            s1=P("dp", "tp", None, None),
            z2=z2 if second else None,
            s2=s2 if second else None,
        )

    def state_health(self, cache, cfg):
        """Moment-state health: every moment finite AND the token-count
        moment non-negative (``n0`` is a running count — a negative value
        means the state was corrupted or merged wrongly, even if finite).

        Args:
          cache: ``TaylorState`` (``z2``/``s2`` None for order-1 configs).
          cfg: model config.

        Returns:
          ``[b]`` bool — True where the row's moments are usable.
        """
        from repro.backends.state import tree_slot_health  # noqa: PLC0415

        finite = tree_slot_health(cache)
        return finite & (cache.n0 >= 0).all(axis=-1)

    def merge_state(self, a, b):
        return merge_states(a, b)

    def apply_cp(self, q, k, v, cfg, mesh, axis, dp_axis=None):
        from repro.core.context_parallel import (  # noqa: PLC0415 (cycle)
            taylor_attention_context_parallel,
        )

        return taylor_attention_context_parallel(
            q, k, v, cfg.taylor, mesh, axis, chunk=cfg.attn_chunk,
            dp_axis=dp_axis,
        )

    def _maybe_cp(self, q, k, v, cfg):
        """Context parallelism when a sharding context is active and the
        sequence divides (shards × chunk); None → caller falls back."""
        from repro.distributed import api as dist  # noqa: PLC0415 (cycle)

        ctx = dist.active()
        if ctx is None:
            return None
        mesh, rules = ctx
        seq_ax = rules.get("sp") or rules.get("tp")
        n = q.shape[2]
        if seq_ax is None or n % (
            dist.mesh_axis_size(mesh, seq_ax) * cfg.attn_chunk
        ) != 0:
            return None
        return self.apply_cp(q, k, v, cfg, mesh, seq_ax, dp_axis=rules.get("dp"))

    # -- cross-attention -----------------------------------------------------

    def init_cross_cache(self, cfg, batch, n_src, dtype):
        hk, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        return init_taylor_state(batch, hk, hd, hd, cfg.taylor)

    def cross_state(self, k, v, cfg):
        return taylor_prefill_state(k, v, cfg.taylor)

    def cross_read(self, state, q, cfg):
        return taylor_state_read(state, q, cfg.taylor)
