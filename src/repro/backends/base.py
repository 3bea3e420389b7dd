"""The attention-backend protocol: one contract for every way this repo
computes attention (exact softmax, the paper's Taylor linear attention,
the elu+1 baseline, and the Mamba/SSD state-space block).

A backend is a stateless singleton describing ONE attention algorithm:
how to run it over a full sequence (train / encoder / parallel prefill),
how to prefill a prompt into a decode state, how to advance that state by
one token, and how to merge per-shard states (context parallelism).  The
model layer (``models/attention.py``, ``models/blocks.py``,
``models/lm.py``), the serve engine (``serve/slots.py``) and the
context-parallel wrapper (``core/context_parallel.py``) resolve backends
exclusively through the registry (``repro.backends.registry``) — adding a
backend means registering one object here, not editing N dispatch chains.

Two protocol levels (the ``level`` flag):

  * ``"qkv"``   — attention proper: methods take projected q/k/v heads
    (``[b, h, n, d]`` / single-token ``[b, h, d]``).  Projections and the
    output matmul stay in ``models/attention.py``.
  * ``"block"`` — the SSM backend: Mamba fuses its projections, conv and
    scan, so its methods take the block params and ``[b, n, d_model]``
    activations instead (see ``backends/ssm.py``).

Capability flags are declarative so dispatch sites (and the registry's
config validation) never need backend-specific ``if`` chains:

  * ``state_kind``     — ``"kv"`` (O(n) KV cache), ``"moments"`` (the
    paper's O(1) Taylor moment state), ``"ssm"`` (O(1) SSD state).
  * ``supports_cross`` — can serve as the cross-attention op of
    encoder-decoder / VLM blocks.
  * ``supports_cp``    — has a context-parallel execution (sequence
    sharded, constant-size state exchanged).
  * ``impls``          — execution engines selectable via
    ``ModelConfig.attn_impl`` ("auto" resolves per platform/envelope).
  * ``state_dtypes``   — slot-state representations the serve layer may
    hold this backend's decode state in (``"dense"`` always; the Taylor
    backend adds ``"int8"``/``"fp8"`` quantised moments).
  * ``supports_paged_kv`` — the backend's ``state_kind="kv"`` slot cache
    may be held paged (pow2 pages + per-slot page table) by the serve
    layer (``serve/state_repr.py``).
  * ``bounded_state``  — decode state is O(1)/O(window) in context length
    (gates ``ModelConfig.supports_long_context`` per layer).

Models need not be single-backend: ``ModelConfig.attention_schedule``
assigns a registered backend per pattern position, and the model / serve
layers resolve a backend PER RUN (``config.schedule_runs``) — mixed
``state_kind`` caches coexist in one slot store (docs/serving.md
§Hybrid schedules).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


class AttentionBackend:
    """Base class + protocol of one attention algorithm (see module doc).

    Subclasses override the class-level capability flags and the protocol
    methods; instances are registered via
    ``repro.backends.registry.register_backend`` and resolved with
    ``get_backend(name)`` / ``resolve_backend(cfg)``.
    """

    name: str = ""
    level: str = "qkv"  # "qkv" | "block"
    state_kind: str = "kv"  # "kv" | "moments" | "ssm"
    supports_cross: bool = False
    supports_cp: bool = False
    impls: Tuple[str, ...] = ("xla",)
    # Serve-layer slot-state representations (docs/serving.md §Memory):
    # which lossy/compact encodings of this backend's decode state the
    # engine may hold between dispatches.  The compute path always runs
    # dense (fp32 accumulate); these flags only gate what
    # ``ServeEngine(state_dtype=..., kv_page_size=...)`` accepts.
    state_dtypes: Tuple[str, ...] = ("dense",)
    supports_paged_kv: bool = False

    @property
    def bounded_state(self) -> bool:
        """True when decode state is O(1) in context length.

        The per-layer gate behind ``ModelConfig.supports_long_context``:
        moment/SSM states are constant-size, a full KV cache is O(n).
        Backends with a bounded KV ring (``softmax_window``: O(window))
        override this to True despite ``state_kind == "kv"``."""
        return self.state_kind != "kv"

    # -- config validation / impl selection ---------------------------------

    def validate(self, cfg) -> None:
        """Raise ``ValueError`` for configs this backend cannot execute.

        Called by ``registry.resolve_backend`` — the single choke point
        where capability flags are enforced (impl availability, cross /
        context-parallel support, kernel envelopes)."""
        if cfg.attn_impl != "auto" and cfg.attn_impl not in self.impls:
            raise ValueError(
                f"attention backend {self.name!r} has impls {self.impls}; "
                f"attn_impl={cfg.attn_impl!r} is not one of them"
            )
        uses_cross = self._uses_cross(cfg)
        if uses_cross and not self.supports_cross:
            raise ValueError(
                f"attention backend {self.name!r} does not support "
                f"cross-attention (supports_cross=False) but the model has "
                f"cross blocks: {cfg.pattern + cfg.tail}"
            )
        if cfg.attn_sharding == "cp" and not self.supports_cp:
            raise ValueError(
                f"attention backend {self.name!r} does not support context "
                "parallelism (supports_cp=False); use attn_sharding='tp'"
            )

    @staticmethod
    def _uses_cross(cfg) -> bool:
        kinds = cfg.pattern + cfg.tail + cfg.encoder_pattern
        return "cross" in kinds or cfg.family in ("vlm", "encdec")

    def resolve_impl(self, cfg) -> str:
        """Concrete impl for this run: ``cfg.attn_impl`` unless "auto"."""
        if cfg.attn_impl != "auto":
            return cfg.attn_impl
        return self.impls[0]

    def draft_config(self, cfg):
        """Cheaper same-weights config for speculative self-drafting.

        The order hierarchy the paper introduces gives some backends a
        natural draft model sharing the target's weights: the Taylor
        backend drops the order-2 moment terms (``S2``/``z2``) and drafts
        with the order-1 feature map.  Returns the draft ``ModelConfig``
        (same params, lighter per-slot state) or ``None`` when this
        backend has no cheap self-draft — the serve layer then rejects
        ``draft="order1"`` requests at submit time
        (docs/serving.md §Speculative decoding).

        Args:
          cfg: the target model config.

        Returns:
          A draft ``ModelConfig`` or ``None``.
        """
        return None

    # -- protocol: full-sequence / prefill / decode / state -----------------

    def init_cache(self, cfg, batch: int, n_max: int, dtype) -> Any:
        """Zero decode state for ``batch`` rows (``n_max`` = KV capacity in
        tokens; ignored by O(1)-state backends)."""
        raise NotImplementedError(self.name)

    def apply(self, q: Array, k: Array, v: Array, cfg, *, causal: bool = True) -> Array:
        """Full-sequence attention (training / encoder / parallel prefill).

        q ``[b, h, n, d]``; k/v ``[b, hk, n, ·]`` (GQA: ``h % hk == 0``).
        ``causal`` is the EFFECTIVE causality (cross-attention passes
        False).  Returns ``[b, h, n, dv]``."""
        raise NotImplementedError(self.name)

    def prefill(self, q: Array, k: Array, v: Array, cfg, n_max: int):
        """Causal full-sequence pass that also returns the decode state:
        ``(out [b, h, n, dv], cache)`` — the exact state token-by-token
        decode would have reached after the prompt."""
        raise NotImplementedError(self.name)

    def decode_step(self, cache, q: Array, k: Array, v: Array, cfg, pos: Array):
        """One autoregressive step against the state.

        q ``[b, h, d]``; k/v ``[b, hk, ·]``; pos ``[b]`` int32 0-based
        position of this token (per batch row / serving slot).  The new
        token attends to itself.  Returns ``(out [b, h, dv], new_cache)``."""
        raise NotImplementedError(self.name)

    def in_place_leaf(self, cfg, stack) -> Optional[str]:
        """The leaf of a run's stacked decode cache that ``decode_step``
        takes whole, as a ``state.StackedLeaf``, and updates in place.

        The model's decode scan slices every other leaf to the layer and
        writes it back; a leaf named here crosses HBM only inside the
        backend's own pass over it (the Taylor decode kernel's second
        moment).

        Args:
          cfg: the run's layer config.
          stack: the run's stacked cache (leaves ``[n_groups, run_len,
            b, ...]``; arrays or shapes).

        Returns:
          The leaf's field name, or ``None`` (default): every leaf is
          sliced per layer.
        """
        return None

    def prefill_chunk(self, cache, q: Array, k: Array, v: Array, cfg, pos: Array):
        """Advance a decode state by a CHUNK of prompt tokens in one call.

        The chunked-prefill building block (serving: long-prompt admission
        must not monopolise the device between decode blocks — see
        docs/serving.md §Chunked prefill).  Semantically identical to
        ``decode_step`` applied token by token over the chunk; backends
        override it with a batched form when one exists (the Taylor chunk
        scan continues from ``cache`` via ``initial_state``).

        Args:
          cache: decode state to continue from (``init_cache`` zeros or the
            state of the previous chunk).
          q: chunk queries ``[b, h, c, d]``.
          k: chunk keys ``[b, hk, c, d]`` (``h % hk == 0``).
          v: chunk values ``[b, hk, c, dv]``.
          cfg: model config.
          pos: ``[b, c]`` int32 absolute 0-based positions of the chunk
            tokens (per batch row).

        Returns:
          ``(out [b, h, c, dv], new_cache)`` — ``out[:, :, i]`` attends to
          every chunk token ``<= i`` plus everything already in ``cache``
          (inclusive causal semantics, matching ``decode_step``).
        """

        def body(cache, xs):
            q_t, k_t, v_t, p_t = xs
            o_t, cache = self.decode_step(cache, q_t, k_t, v_t, cfg, p_t)
            return cache, o_t

        xs = (
            jnp.moveaxis(q, 2, 0),
            jnp.moveaxis(k, 2, 0),
            jnp.moveaxis(v, 2, 0),
            jnp.moveaxis(pos, 1, 0),
        )
        cache, outs = jax.lax.scan(body, cache, xs)
        return jnp.moveaxis(outs, 0, 2), cache

    def merge_state(self, a, b):
        """Merge the states of two CONSECUTIVE sequence shards (context
        parallelism).  Only meaningful when ``supports_cp``."""
        raise NotImplementedError(
            f"attention backend {self.name!r} has no mergeable state "
            "(supports_cp=False)"
        )

    def apply_cp(self, q: Array, k: Array, v: Array, cfg, mesh, axis: str,
                 dp_axis=None) -> Array:
        """Context-parallel full-sequence attention: sequence sharded over
        mesh ``axis``, O(1) state exchanged.  Only when ``supports_cp``."""
        raise NotImplementedError(
            f"attention backend {self.name!r} does not support context "
            "parallelism"
        )

    def state_health(self, cache, cfg) -> Array:
        """Per-row health of a decode state (serving corruption guard).

        A cheap, jit-safe predicate the serve engine sweeps after decode
        blocks: a row whose state went non-finite (NaN/Inf moments, KV, or
        SSM state) poisons every future token of that slot, so the engine
        quarantines it and re-prefills the request (docs/serving.md
        §Failure semantics).  The base implementation checks finiteness of
        every inexact leaf; backends with extra invariants (e.g. the KV
        cache's ``length`` bounds) override and AND them in.  Must be
        O(state size) with no data-dependent control flow — it runs under
        ``jax.jit``/``vmap`` over the stacked block caches.

        Args:
          cache: decode state as built by ``init_cache`` (or a
            cross-attention read state — same leaf layout).
          cfg: model config.

        Returns:
          ``[b]`` bool — True where the row's state is usable.
        """
        from repro.backends.state import tree_slot_health  # noqa: PLC0415

        return tree_slot_health(cache)

    # -- protocol: decode-state sharding (mesh serving) ----------------------

    def cache_pspec(self, cfg):
        """LOGICAL partition axes of this backend's decode state.

        A pytree congruent to ``init_cache``'s output whose leaves are
        ``PartitionSpec``s of *logical* axis names ("dp" = the batch/slot
        axis, "tp" = the head axis) — resolved to physical mesh axes,
        divisibility-aware, by ``distributed.sharding.slot_cache_specs``.
        The resolver moves a dropped "tp" to the leaf's LAST dim when that
        divides instead (MQA: 1 kv head collapses the head axis, so Taylor
        moment states shard over d_v).

        The base implementation describes the KV-cache layout
        (``state_kind="kv"`` backends: slots over dp, kv heads over tp);
        O(1)-state backends override it alongside ``init_cache``.

        Args:
          cfg: model config.

        Returns:
          Pytree of logical ``PartitionSpec`` leaves congruent to
          ``init_cache(cfg, ...)``.
        """
        from repro.backends.state import kv_cache_pspec  # noqa: PLC0415

        return kv_cache_pspec()

    def cross_cache_pspec(self, cfg):
        """Logical partition axes of the cross-attention read state.

        Defaults to ``cache_pspec`` — every built-in backend's cross state
        has the same pytree structure as its self-attention decode state
        (``init_cross_cache`` mirrors ``init_cache``).

        Args:
          cfg: model config.

        Returns:
          Pytree of logical ``PartitionSpec`` leaves congruent to
          ``init_cross_cache(cfg, ...)``.
        """
        return self.cache_pspec(cfg)

    # -- protocol: cross-attention state (supports_cross backends) ----------

    def init_cross_cache(self, cfg, batch: int, n_src: int, dtype):
        """Zero cross-attention state for a source of ``n_src`` tokens."""
        raise NotImplementedError(
            f"attention backend {self.name!r} does not support cross-attention"
        )

    def cross_state(self, k: Array, v: Array, cfg):
        """Precompute the cross-attention read state from projected source
        k/v ``[b, hk, n_src, ·]`` (encoder output / vision tokens)."""
        raise NotImplementedError(
            f"attention backend {self.name!r} does not support cross-attention"
        )

    def cross_read(self, state, q: Array, cfg) -> Array:
        """Read one decode step's cross-attention: q ``[b, h, d]`` against
        the precomputed state.  Returns ``[b, h, dv]``."""
        raise NotImplementedError(
            f"attention backend {self.name!r} does not support cross-attention"
        )
