"""Inference engine: compiled building blocks + compatibility wrappers.

The serving execution model is continuous batching (``scheduler.py``):
``max_slots`` requests decode together from a slot-indexed cache
(``slots.py``), and ``decode_scan`` advances ALL slots by a block of tokens
in ONE device dispatch — a ``jax.lax.scan`` over ``lm_decode_step`` with
per-slot position, stop and sampling state.  This file owns the compiled
pieces; the scheduler owns admission and slot lifecycle.

``build_decode_scan`` is the mesh-aware compilation point (the sharded
engine pins the slotted-cache shardings so donation stays in place);
``prefill_chunked`` is the bounded-dispatch admission path for long
prompts (docs/serving.md §Chunked prefill).

``generate`` is kept as a thin compatibility wrapper over the engine (same
signature as the original per-token loop); ``generate_loop`` preserves the
old one-dispatch-per-token loop as the parity/benchmark baseline.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.lm import (
    lm_decode_step,
    lm_init_caches,
    lm_prefill,
    lm_prefill_chunk,
)

Array = jax.Array


def prefill(params, batch: Dict[str, Array], cfg: ModelConfig, n_max: int):
    """Run the prompt and materialise per-layer decode caches.

    Args:
      params: model params from ``lm_init``.
      batch: ``{"tokens": [b, n] int32, ...}`` plus family extras
        (``image_embeds`` / ``audio_frames``).
      cfg: model config.
      n_max: KV capacity to allocate (softmax backend; the taylor moment
        state is O(1) in context length).

    Returns:
      ``(logits [b, vocab]`` for the last prompt position``, caches)`` —
      the cache pytree ``lm_prefill`` defines.  For the taylor backend the
      caches hold the final chunk-scan moment state (``return_state=True``
      handoff), exactly the state token-by-token decode would have reached.
    """
    return lm_prefill(params, batch, cfg, n_max)


def decode_step(params, token_t: Array, caches, pos, cfg: ModelConfig):
    """Advance one token for the whole batch.

    Args:
      params: model params.
      token_t: ``[b]`` int32 current tokens.
      caches: cache pytree from ``prefill`` / ``slots.init_slot_caches``.
      pos: scalar or ``[b]`` int32 0-based position of ``token_t``.
      cfg: model config.

    Returns:
      ``(logits [b, vocab], new caches)``.
    """
    return lm_decode_step(params, token_t, caches, pos, cfg)


# jax.jit wrappers cached per (cfg, ...): rebuilding them inside generate()
# discards jit's compilation cache and re-traces prefill/decode on EVERY
# generation.  ModelConfig is hashable (frozen dataclass), so it keys cleanly.
@functools.lru_cache(maxsize=32)
def _jitted_prefill(cfg: ModelConfig, n_max: int):
    return jax.jit(functools.partial(lm_prefill, cfg=cfg, n_max=n_max))


@functools.lru_cache(maxsize=32)
def _jitted_decode_step(cfg: ModelConfig):
    return jax.jit(functools.partial(lm_decode_step, cfg=cfg), donate_argnums=(2,))


@functools.lru_cache(maxsize=32)
def _jitted_slot_health(cfg: ModelConfig):
    # One fused reduction over the whole slotted cache ([max_slots] bool).
    # Read-only (nothing donated) so the same compilation serves the
    # single-device and mesh engines — shardings derive from the input.
    from repro.serve.slots import slot_health  # noqa: PLC0415 (cycle)

    return jax.jit(functools.partial(slot_health, cfg=cfg))


@functools.lru_cache(maxsize=32)
def _jitted_prefill_chunk(cfg: ModelConfig):
    # donate the caches: every chunk fully replaces them, and a long-prompt
    # admission would otherwise hold two copies of the KV leaves alive.
    return jax.jit(
        functools.partial(lm_prefill_chunk, cfg=cfg), donate_argnums=(2,)
    )


def prefill_chunked(
    params,
    batch: Dict[str, Array],
    cfg: ModelConfig,
    n_max: int,
    chunk: int,
    cache_dtype=None,
):
    """Whole-prompt prefill as a sequence of bounded chunk dispatches.

    Same contract as ``prefill`` — ``(last-token logits [b, vocab],
    caches)``, matching it to fp tolerance — but no single device dispatch
    processes more than ``chunk`` prompt tokens.  This is the long-prompt
    admission path of the serve engine: between chunks the scheduler can
    keep advancing in-flight decode slots, so a 500k-token prompt no
    longer freezes every other stream for the whole prefill (see
    docs/serving.md §Chunked prefill).

    Decoder-only models only (``cfg.family == "lm"``): vlm/encdec prompts
    need their source state built by ``lm_prefill`` from the request
    extras.

    Args:
      params: model params.
      batch: ``{"tokens": [b, n] int32}`` (no extras — see above).
      cfg: model config.
      n_max: per-slot KV capacity to allocate.
      chunk: prompt tokens per dispatch (the admission budget; the final
        chunk may be shorter).
      cache_dtype: KV-cache dtype (defaults to ``cfg.dtype``).

    Returns:
      ``(logits [b, vocab]`` of the last prompt position``, caches)`` —
      the same pytree structure ``prefill`` returns.
    """
    if cfg.family != "lm":
        raise ValueError(
            f"prefill_chunked supports decoder-only models; family "
            f"{cfg.family!r} prompts carry source extras that whole-prompt "
            "prefill must build (use prefill)"
        )
    if chunk < 1:
        raise ValueError("chunk must be >= 1")
    tokens = jnp.asarray(batch["tokens"], jnp.int32)
    b, n = tokens.shape
    dtype = jnp.dtype(cache_dtype or cfg.dtype)
    caches = lm_init_caches(cfg, b, n_max, dtype)
    step = _jitted_prefill_chunk(cfg)
    logits = None
    for s in range(0, n, chunk):
        logits, caches = step(
            params, tokens[:, s : s + chunk], caches, jnp.asarray(s, jnp.int32)
        )
    return logits, caches


# ---------------------------------------------------------------------------
# Per-slot sampling
# ---------------------------------------------------------------------------


def sample_tokens(
    logits: Array,
    rng: Array,
    temperature: Array,
    top_k: Array,
    max_top_k: Optional[int] = None,
) -> Array:
    """Per-slot next-token sampling: greedy / temperature / top-k.

    Args:
      logits: ``[s, vocab]`` f32 next-token logits (one row per slot).
      rng: PRNG key consumed by the categorical draw.
      temperature: ``[s]`` f32; ``0`` selects greedy argmax for that slot.
      top_k: ``[s]`` int32; ``> 0`` restricts sampling to the k
        highest-logit tokens for that slot, ``0`` disables the filter.
      max_top_k: static upper bound on ``top_k`` (the scheduler knows it
        host-side).  ``0`` skips the top-k threshold entirely; ``None``
        falls back to a full-vocab sort (general but O(V log V) — avoid
        in compiled hot loops).

    Returns:
      ``[s]`` int32 sampled tokens.
    """
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if max_top_k is None or max_top_k > 0:
        # Per-slot k-th largest logit as the top-k admission threshold:
        # lax.top_k with the static bound is O(V·k); the sort fallback is
        # the arbitrary-k escape hatch.
        if max_top_k is None:
            desc = jnp.sort(logits, axis=-1)[:, ::-1]
        else:
            desc, _ = jax.lax.top_k(logits, min(max_top_k, vocab))
        kth = jnp.take_along_axis(
            desc, jnp.clip(top_k - 1, 0, desc.shape[-1] - 1)[:, None], axis=-1
        )
        logits = jnp.where((top_k[:, None] > 0) & (logits < kth), -jnp.inf, logits)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(rng, scaled).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


# ---------------------------------------------------------------------------
# Compiled multi-token decode: one dispatch advances all slots by `steps`.
# ---------------------------------------------------------------------------


def _decode_scan_fn(cfg: ModelConfig, steps: int, sampling: bool, max_top_k: int,
                    codec=None):
    """The (unjitted) ``steps``-token decode body shared by the
    single-device and mesh-sharded compilations.

    With ``codec`` (a ``serve.state_repr`` state codec) the caches arrive
    and leave in the STORED representation: the body decodes to dense
    once per dispatch, runs the fp32-accumulate scan unmodified, and
    re-encodes once at the end — quantisation/paging cost is per block,
    not per token."""

    def scan_fn(params, caches, token, pos, active, temperature, top_k, eos_id, rng):
        stored = caches
        if codec is not None:
            caches = codec.decode(stored)
        # Slots inactive at DISPATCH time keep their pre-dispatch state bit
        # for bit: every step updates only the rows of ``active_in``.  A
        # speculative slot advanced by a verify this block is LIVE while
        # excluded from the decode mask, so its state must not churn.  The
        # mask is applied inside each layer's in-place update, so no
        # snapshot of the whole state is held across the dispatch.
        active_in = active

        def body(carry, _):
            token, caches, pos, active, rng = carry
            logits, caches = lm_decode_step(params, token, caches, pos, cfg,
                                            keep=active_in)
            if sampling:
                rng, sub = jax.random.split(rng)
                nxt = sample_tokens(
                    logits, sub, temperature, top_k,
                    None if max_top_k < 0 else max_top_k,
                )
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # Inactive slots freeze: token and position stop advancing, so
            # their (dead) state churn can never run out of bounds.
            nxt = jnp.where(active, nxt, token)
            pos = jnp.where(active, pos + 1, pos)
            emitted = active
            active = active & (nxt != eos_id)
            return (nxt, caches, pos, active, rng), (nxt, emitted)

        (token, caches, pos, active, rng), (toks, mask) = jax.lax.scan(
            body, (token, caches, pos, active, rng), None, length=steps
        )
        if codec is not None:
            caches = codec.encode(caches, stored)
        return caches, token, pos, active, rng, toks, mask

    return scan_fn


@functools.lru_cache(maxsize=64)
def _jitted_decode_scan(cfg: ModelConfig, steps: int, sampling: bool,
                        max_top_k: int, codec=None):
    """Compiled ``steps``-token decode over all slots (see ``decode_scan``).

    ``sampling``/``max_top_k`` are static specializations the scheduler
    derives host-side from the occupied slots: the all-greedy common case
    compiles to a pure argmax body (no rng, no sort/top_k).  ``codec``
    (hashable, frozen) keys the stored-representation variants."""
    return jax.jit(_decode_scan_fn(cfg, steps, sampling, max_top_k, codec),
                   donate_argnums=(1,))


def build_decode_scan(
    cfg: ModelConfig,
    steps: int,
    sampling: bool,
    max_top_k: int,
    cache_shardings=None,
    codec=None,
):
    """Compile one ``decode_scan`` variant, optionally mesh-sharded.

    With ``cache_shardings`` the cache output is PINNED to the slotted
    layout (``slot_cache_shardings``) and the per-slot control vectors
    (token/pos/active/…) to replicated — pinning is what makes the donated
    cache buffer reusable in place across dispatches instead of being
    re-laid-out by the partitioner.  Without it this is exactly the
    single-device compilation ``decode_scan`` uses (shared lru cache).

    Args:
      cfg: model config (static).
      steps: tokens per dispatch (static).
      sampling: static — False compiles the argmax-only body.
      max_top_k: static top-k bound (``-1`` = full-vocab sort fallback).
      cache_shardings: ``NamedSharding`` pytree for the slotted cache
        (STORED representation when a codec is active), or None for the
        single-device engine.
      codec: optional ``serve.state_repr`` codec — the caches flow
        through the dispatch in their stored representation.

    Returns:
      A jitted callable with ``decode_scan``'s positional signature
      (params, caches, token, pos, active, temperature, top_k, eos_id,
      rng), caches donated.
    """
    if cache_shardings is None:
        return _jitted_decode_scan(cfg, steps, bool(sampling), int(max_top_k),
                                   codec)
    mesh = jax.tree_util.tree_leaves(cache_shardings)[0].mesh
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    out_shardings = (cache_shardings, rep, rep, rep, rep, rep, rep)
    return jax.jit(
        _decode_scan_fn(cfg, steps, bool(sampling), int(max_top_k), codec),
        donate_argnums=(1,),
        out_shardings=out_shardings,
    )


def decode_scan(
    params,
    caches,
    token: Array,
    pos: Array,
    active: Array,
    temperature: Array,
    top_k: Array,
    eos_id: Array,
    rng: Array,
    cfg: ModelConfig,
    steps: int,
    sampling: bool = True,
    max_top_k: Optional[int] = None,
):
    """Advance every slot by ``steps`` tokens in one compiled dispatch.

    A ``lax.scan`` over ``lm_decode_step``: per step each ACTIVE slot feeds
    its current token at its own position, samples the next token
    (greedy/temperature/top-k per slot), and goes inactive when it emits its
    ``eos_id``.  Inactive slots freeze (token/pos held), so one dispatch
    safely mixes slots at different lifecycle stages.

    Args:
      params: model params.
      caches: slotted cache pytree (donated).
      token: ``[s]`` int32 current token per slot.
      pos: ``[s]`` int32 position of ``token`` per slot.
      active: ``[s]`` bool — slots that should decode.
      temperature: ``[s]`` f32 sampling temperature (0 = greedy).
      top_k: ``[s]`` int32 top-k filter (0 = off).
      eos_id: ``[s]`` int32 stop token (-1 = never stops).
      rng: PRNG key (split once per step).
      cfg: model config (static).
      steps: tokens to advance (static — compiled once per value).
      sampling: static — False compiles a pure-argmax body (all slots
        greedy), skipping rng and the top-k machinery entirely.
      max_top_k: static upper bound on ``top_k`` (see ``sample_tokens``).

    Returns:
      ``(caches, token, pos, active, rng, toks [steps, s], mask
      [steps, s])`` — ``toks[t, s]`` is valid output iff ``mask[t, s]``.
    """
    k = -1 if max_top_k is None else int(max_top_k)
    fn = _jitted_decode_scan(cfg, steps, bool(sampling), k)
    return fn(params, caches, token, pos, active, temperature, top_k, eos_id, rng)


# ---------------------------------------------------------------------------
# Generation wrappers
# ---------------------------------------------------------------------------


def generate(
    params,
    batch: Dict[str, Array],
    cfg: ModelConfig,
    steps: int,
    n_max: Optional[int] = None,
    greedy: bool = True,
    rng: Optional[Array] = None,
) -> Array:
    """Greedy/sampled generation — thin wrapper over the serve engine.

    Each batch row becomes one engine request; all rows share a prompt
    length, so they are admitted together and decode as one continuously
    batched group (token-identical to the old per-token loop for greedy
    decoding — tested).

    Args:
      params: model params.
      batch: ``{"tokens": [b, n] int32, ...}`` plus family extras.
      cfg: model config.
      steps: number of new tokens to generate.
      n_max: KV capacity (default ``prompt_len + steps``).
      greedy: argmax decoding when True; otherwise temperature-1 sampling
        driven by ``rng``.
      rng: PRNG key for sampled decoding.

    Returns:
      ``[b, steps]`` int32 new tokens.
    """
    from repro.serve.scheduler import Request, ServeEngine  # noqa: PLC0415 (cycle)

    import numpy as np  # noqa: PLC0415

    prompt = np.asarray(batch["tokens"])
    b, prompt_len = prompt.shape
    n_max = n_max or (prompt_len + steps)
    temperature = 0.0 if (greedy or rng is None) else 1.0
    eng = ServeEngine(
        params, cfg, max_slots=b, n_max=n_max,
        decode_block=min(steps, 16) or 1, rng=rng,
    )
    rids = [
        eng.submit(Request(
            tokens=prompt[i],
            max_new_tokens=steps,
            temperature=temperature,
            extras={k: np.asarray(v)[i : i + 1]
                    for k, v in batch.items() if k != "tokens"},
        ))
        for i in range(b)
    ]
    outs = eng.run()
    return jnp.stack([jnp.asarray(outs[r], jnp.int32) for r in rids])


def generate_loop(
    params,
    batch: Dict[str, Array],
    cfg: ModelConfig,
    steps: int,
    n_max: Optional[int] = None,
    greedy: bool = True,
    rng: Optional[Array] = None,
) -> Array:
    """The original per-token decode loop (one jit dispatch per token).

    Kept as the parity oracle for the continuous-batching engine and as the
    benchmark baseline (``benchmarks/bench_serve.py``).  Same contract as
    ``generate``.

    Args:
      params, batch, cfg, steps, n_max, greedy, rng: see ``generate``.

    Returns:
      ``[b, steps]`` int32 new tokens.
    """
    prompt_len = batch["tokens"].shape[1]
    n_max = n_max or (prompt_len + steps)
    prefill_fn = _jitted_prefill(cfg, n_max)
    step_fn = _jitted_decode_step(cfg)
    logits, caches = prefill_fn(params, batch)
    outs = []
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for i in range(steps):
        outs.append(token)
        if i == steps - 1:
            break
        pos = jnp.asarray(prompt_len + i, jnp.int32)
        logits, caches = step_fn(params, token, caches, pos)
        if greedy or rng is None:
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            rng, sub = jax.random.split(rng)
            token = jax.random.categorical(sub, logits).astype(jnp.int32)
    return jnp.stack(outs, axis=1)
