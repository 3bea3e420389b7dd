"""Model assembly: decoder-only LM, encoder-decoder (whisper-style), and VLM
(cross-attention) variants — all expressed as a repeating block ``pattern``
scanned over ``n_groups`` (+ optional ``tail``), so HLO size is O(1) in depth.

Inputs are a dict:
  tokens        [b, n]  int32          (always)
  labels        [b, n]  int32          (training)
  image_embeds  [b, n_img, vision_dim] (vlm; stub vision tower output)
  audio_frames  [b, n_audio, d_model]  (encdec; stub conv-frontend output)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.api import constrain
from repro.models.blocks import (
    block_apply,
    block_decode,
    block_init,
    block_prefill,
    block_prefill_chunk,
)
from repro.models.config import ModelConfig, schedule_runs
from repro.models.layers import (
    dense_init,
    sinusoidal_pos,
    embed_apply,
    embed_init,
    norm_apply,
    norm_init,
    softcap,
    trunc_normal,
    unembed_apply,
)

Array = jax.Array


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _runs(kinds):
    """Collapse a pattern into runs of equal kinds: [('mamba', 6), ('shared_attn', 1)].

    Each non-shared run is applied with an inner lax.scan so XLA cannot hoist
    several blocks' remat recomputations into one live window (that
    scheduler freedom is what blew zamba2's backward to 7× one block's
    working set; see EXPERIMENTS.md §Perf)."""
    out = []
    for kind in kinds:
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1] + 1)
        else:
            out.append((kind, 1))
    return tuple(out)


def _cfg_runs(cfg: ModelConfig, kinds=None):
    """Runs of ``(kind, run_cfg, run_len)``.

    A run's scan body is traced ONCE, so every block in a run must share an
    attention backend; ``attention_schedule`` entries split the decoder
    pattern's runs where the backend changes (``config.schedule_runs``) and
    each run carries its uniform ``layer_cfg`` view.  Pass ``kinds`` for
    patterns the schedule does not apply to (encoder, tail)."""
    if kinds is not None:
        return tuple((k, cfg, rl) for k, rl in _runs(kinds))
    return tuple(
        (k, cfg.layer_cfg(bk), rl) for k, bk, rl in schedule_runs(cfg)
    )


def _stack_init(key, runs, n_groups: int, dtype):
    """Init one stacked param set per pattern RUN: leaves [n_groups, run_len, ...]."""
    out = {}
    for j, (kind, rcfg, rl) in enumerate(runs):
        if kind == "shared_attn":
            continue  # shared weights live outside the stack
        keys = jax.random.split(jax.random.fold_in(key, j), n_groups * rl).reshape(
            n_groups, rl, 2
        )
        out[f"r{j}"] = jax.vmap(
            jax.vmap(lambda k: block_init(k, kind, rcfg, dtype))
        )(keys)
    return out


def lm_init(key, cfg: ModelConfig, dtype=None):
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 10)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.d_model, cfg.norm, dtype),
        "blocks": {"group": _stack_init(ks[1], _cfg_runs(cfg), cfg.n_groups, dtype)},
    }
    if cfg.tail:
        params["blocks"]["tail"] = {
            f"t{i}": block_init(jax.random.fold_in(ks[2], i), kind, cfg, dtype)
            for i, kind in enumerate(cfg.tail)
            if kind != "shared_attn"
        }
    if "shared_attn" in cfg.pattern + cfg.tail:
        params["blocks"]["shared"] = block_init(ks[3], "shared_attn", cfg, dtype)
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(ks[4], cfg.vocab, cfg.d_model, dtype)
    if cfg.pos == "learned":
        params["pos_embed"] = trunc_normal(ks[5], (cfg.max_seq, cfg.d_model), 0.01, dtype)
    if cfg.family == "vlm":
        params["vision_proj"] = dense_init(ks[6], (cfg.vision_dim, cfg.d_model), dtype=dtype)
    if cfg.family == "encdec":
        params["encoder"] = {
            "group": _stack_init(
                ks[7], _cfg_runs(cfg, cfg.encoder_pattern), cfg.n_encoder_groups, dtype
            ),
            "final_norm": norm_init(cfg.d_model, cfg.norm, dtype),
        }
        if cfg.pos == "learned":
            params["encoder"]["pos_embed"] = trunc_normal(
                ks[8], (cfg.n_audio_ctx, cfg.d_model), 0.01, dtype
            )
    return params


# ---------------------------------------------------------------------------
# Stack application (scan over groups)
# ---------------------------------------------------------------------------


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots_saveable":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    raise ValueError(cfg.remat)


def _stack_apply(
    blocks,
    runs,
    x: Array,
    cfg: ModelConfig,
    positions: Optional[Array],
    kv_src: Optional[Array],
    causal: bool,
) -> Tuple[Array, Array]:
    shared = blocks.get("shared")
    group = blocks["group"]

    # Remat at BLOCK granularity; blocks of one run execute under an inner
    # lax.scan, so backward recomputation is strictly one block at a time.
    # One fn per (kind, backend): each run applies its own layer_cfg view.
    def one_block(p, x, kind, rcfg):
        x, a = block_apply(p, kind, x, rcfg, positions, kv_src, causal)
        return constrain(x, "dp", "sp", None), a

    tail_cfg = cfg.layer_cfg(cfg.attention)
    fn_cfgs = {(kind, rcfg.attention): rcfg for kind, rcfg, _ in runs}
    for kind in cfg.tail:
        fn_cfgs.setdefault((kind, cfg.attention), tail_cfg)
    block_fns = {
        key: _remat(functools.partial(one_block, kind=key[0], rcfg=rcfg), cfg)
        for key, rcfg in fn_cfgs.items()
    }

    def run_scan(kind, bk, rl, x, aux, run_params):
        def body(carry, p):
            x, aux = carry
            x, a = block_fns[(kind, bk)](shared if kind == "shared_attn" else p, x)
            return (x, aux + a), None

        xs = None if kind == "shared_attn" else run_params
        (x, aux), _ = jax.lax.scan(body, (x, aux), xs, length=rl)
        return x, aux

    def group_body(carry, group_params):
        x, aux = carry
        for j, (kind, rcfg, rl) in enumerate(runs):
            rp = None if kind == "shared_attn" else group_params[f"r{j}"]
            x, aux = run_scan(kind, rcfg.attention, rl, x, aux, rp)
        return (x, aux), None

    aux0 = jnp.zeros((), jnp.float32)
    if group:
        (x, aux), _ = jax.lax.scan(group_body, (x, aux0), group)
    else:
        aux = aux0
    for i, kind in enumerate(cfg.tail):
        p = shared if kind == "shared_attn" else blocks["tail"][f"t{i}"]
        x, a = block_fns[(kind, cfg.attention)](p, x)
        aux = aux + a
    return x, aux


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _embed_tokens(params, tokens: Array, cfg: ModelConfig) -> Array:
    dtype = jnp.dtype(cfg.dtype)
    x = embed_apply(params["embed"], tokens, dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, dtype)
    if cfg.pos == "learned":
        x = x + params["pos_embed"][: tokens.shape[1]].astype(dtype)[None]
    elif cfg.pos == "sinusoidal":
        x = x + sinusoidal_pos(jnp.arange(tokens.shape[1]), cfg.d_model).astype(dtype)[None]
    return constrain(x, "dp", "sp", None)


def _encode(params, frames: Array, cfg: ModelConfig) -> Array:
    """Whisper-style encoder over (stubbed) conv-frontend frames."""
    dtype = jnp.dtype(cfg.dtype)
    enc = params["encoder"]
    if cfg.pos == "learned":
        pe = enc["pos_embed"][: frames.shape[1]].astype(dtype)
    else:
        pe = sinusoidal_pos(jnp.arange(frames.shape[1]), cfg.d_model).astype(dtype)
    x = frames.astype(dtype) + pe[None]
    x, _ = _stack_apply(
        enc, _cfg_runs(cfg, cfg.encoder_pattern), x, cfg, None, None, causal=False
    )
    return norm_apply(enc["final_norm"], x, cfg.norm, cfg.norm_eps)


def _kv_source(params, batch: Dict[str, Array], cfg: ModelConfig) -> Optional[Array]:
    if cfg.family == "vlm":
        img = batch["image_embeds"].astype(jnp.dtype(cfg.dtype))
        return jnp.einsum("bnv,vd->bnd", img, params["vision_proj"]["w"].astype(img.dtype))
    if cfg.family == "encdec":
        return _encode(params, batch["audio_frames"], cfg)
    return None


def _logits(params, x: Array, cfg: ModelConfig) -> Array:
    x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed_apply(table, x)
    logits = softcap(logits, cfg.logit_softcap)
    return constrain(logits, "dp", "sp", "tp")


def lm_apply(
    params, batch: Dict[str, Array], cfg: ModelConfig
) -> Tuple[Array, Array]:
    """Full training/eval forward.  Returns (logits [b, n, vocab] fp32, aux)."""
    tokens = batch["tokens"]
    x = _embed_tokens(params, tokens, cfg)
    kv_src = _kv_source(params, batch, cfg)
    positions = jnp.arange(tokens.shape[1])
    x, aux = _stack_apply(
        params["blocks"], _cfg_runs(cfg), x, cfg, positions, kv_src, causal=True
    )
    return _logits(params, x, cfg), aux


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def lm_prefill(
    params, batch: Dict[str, Array], cfg: ModelConfig, n_max: int
) -> Tuple[Array, Any]:
    """Prompt pass.  Returns (logits of last position [b, vocab], caches).

    caches = {"group": stacked-per-group cache pytree, "tail": tuple,
              "kv_src": encoder/vision output or None}
    """
    tokens = batch["tokens"]
    x = _embed_tokens(params, tokens, cfg)
    kv_src = _kv_source(params, batch, cfg)
    positions = jnp.arange(tokens.shape[1])
    blocks = params["blocks"]
    shared = blocks.get("shared")

    runs = _cfg_runs(cfg)

    def group_body(x, group_params):
        caches = []
        for j, (kind, rcfg, rl) in enumerate(runs):
            def run_body(x, p, kind=kind, rcfg=rcfg):
                x, c = block_prefill(
                    shared if kind == "shared_attn" else p,
                    kind, x, rcfg, n_max, positions, kv_src,
                )
                return x, c

            xs = None if kind == "shared_attn" else group_params[f"r{j}"]
            x, run_caches = jax.lax.scan(run_body, x, xs, length=rl)
            caches.append(run_caches)  # leaves [rl, ...]
        return x, tuple(caches)

    if blocks["group"]:
        x, group_caches = jax.lax.scan(group_body, x, blocks["group"])
    else:
        group_caches = ()
    tail_caches = []
    tail_cfg = cfg.layer_cfg(cfg.attention)
    for i, kind in enumerate(cfg.tail):
        p = shared if kind == "shared_attn" else blocks["tail"][f"t{i}"]
        x, c = block_prefill(p, kind, x, tail_cfg, n_max, positions, kv_src)
        tail_caches.append(c)
    logits = _logits(params, x[:, -1:, :], cfg)[:, 0, :]
    caches = {"group": group_caches, "tail": tuple(tail_caches), "kv_src": kv_src}
    return logits, caches


def lm_prefill_chunk(
    params, tokens: Array, caches, pos0, cfg: ModelConfig
) -> Tuple[Array, Any]:
    """Advance the decode caches by a CHUNK of prompt tokens.

    The chunked-prefill step: structurally ``lm_decode_step`` widened to
    ``c`` tokens — the caller loops it over a long prompt so no single
    dispatch exceeds the chunk budget (serving admission must not stall
    in-flight decode slots; see docs/serving.md §Chunked prefill).
    Starting from ``lm_init_caches`` zeros and feeding the whole prompt
    chunk by chunk reproduces ``lm_prefill``'s logits and final state to
    fp tolerance (tested).

    Decoder-only models only: vlm/encdec caches hold source-derived state
    (``kv_src``/cross reads are position-independent, but their caches are
    built by ``lm_prefill`` from the request extras) — the serve engine
    falls back to whole-prompt prefill for those families.

    Args:
      params: model params.
      tokens: ``[b, c]`` int32 chunk of prompt tokens.
      caches: cache pytree from ``lm_init_caches`` (first chunk) or the
        previous ``lm_prefill_chunk`` call.
      pos0: scalar or ``[b]`` int32 absolute position of ``tokens[:, 0]``.
      cfg: model config.

    Returns:
      ``(logits [b, vocab]`` of the chunk's LAST token``, new caches)``.
    """
    x, new = _chunk_hidden(params, tokens, caches, pos0, cfg)
    logits = _logits(params, x[:, -1:, :], cfg)[:, 0, :]
    return logits, new


def _chunk_hidden(params, tokens, caches, pos0, cfg: ModelConfig):
    """Shared chunk-advance body: hidden states [b, c, d] + new caches."""
    dtype = jnp.dtype(cfg.dtype)
    b, c = tokens.shape
    positions = (
        jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (b,))[:, None]
        + jnp.arange(c, dtype=jnp.int32)[None, :]
    )  # [b, c]
    x = embed_apply(params["embed"], tokens, dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, dtype)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(dtype)
    elif cfg.pos == "sinusoidal":
        from repro.models.layers import sinusoidal_pos as _sin  # noqa: PLC0415

        x = x + _sin(positions.reshape(-1), cfg.d_model).reshape(
            b, c, cfg.d_model
        ).astype(dtype)
    blocks = params["blocks"]
    shared = blocks.get("shared")
    runs = _cfg_runs(cfg)

    def group_body(x, xs):
        group_params, group_caches = xs
        new_caches = []
        for j, (kind, rcfg, rl) in enumerate(runs):
            def run_body(x, step_xs, kind=kind, rcfg=rcfg):
                p, cch = step_xs
                return block_prefill_chunk(
                    shared if kind == "shared_attn" else p,
                    kind, x, cch, rcfg, positions,
                )

            rp = None if kind == "shared_attn" else group_params[f"r{j}"]
            x, run_caches = jax.lax.scan(
                run_body, x, (rp, group_caches[j]), length=rl
            )
            new_caches.append(run_caches)
        return x, tuple(new_caches)

    if blocks["group"]:
        x, group_caches = jax.lax.scan(
            group_body, x, (blocks["group"], caches["group"])
        )
    else:
        group_caches = ()
    tail_caches = []
    tail_cfg = cfg.layer_cfg(cfg.attention)
    for i, kind in enumerate(cfg.tail):
        p = shared if kind == "shared_attn" else blocks["tail"][f"t{i}"]
        x, cch = block_prefill_chunk(
            p, kind, x, caches["tail"][i], tail_cfg, positions
        )
        tail_caches.append(cch)
    new = {"group": group_caches, "tail": tuple(tail_caches),
           "kv_src": caches.get("kv_src")}
    return x, new


def lm_verify_chunk(
    params, tokens: Array, caches, pos0, cfg: ModelConfig
) -> Tuple[Array, Any]:
    """Advance the decode caches by a chunk, returning EVERY position's logits.

    The speculative-verify primitive: identical state roll-forward to
    ``lm_prefill_chunk`` (same chunk math, so the returned caches are the
    state token-by-token decode would have built), but the logits head is
    applied to all ``c`` positions instead of only the last one.  The
    caller compares ``argmax(logits[:, j])`` against the drafted token at
    position ``j + 1`` to find the longest greedy-matching prefix — one
    dispatch verifies k proposed tokens (docs/serving.md §Speculative
    decoding).

    Args:
      params: model params.
      tokens: ``[b, c]`` int32 window — last emitted token followed by
        the ``c - 1`` drafted tokens.
      caches: cache pytree whose state has absorbed positions
        ``[0, pos0)``.
      pos0: scalar or ``[b]`` int32 absolute position of ``tokens[:, 0]``.
      cfg: model config.

    Returns:
      ``(logits [b, c, vocab]`` for every window position``, new caches)``
      — the caches have absorbed all ``c`` window tokens.
    """
    x, new = _chunk_hidden(params, tokens, caches, pos0, cfg)
    return _logits(params, x, cfg), new


def _layer_cache(stack, g: Array, r: Array):
    """Layer ``(g, r)``'s cache read out of a run's stacked caches
    (leaves ``[n_groups, run_len, ...]``)."""
    return jax.tree.map(
        lambda s: jax.lax.dynamic_slice(
            s, (g, r) + (0,) * (s.ndim - 2), (1, 1) + s.shape[2:]
        )[0, 0],
        stack,
    )


def _set_layer_cache(stack, new, g: Array, r: Array):
    """``stack`` with layer ``(g, r)``'s cache replaced by ``new``: a
    dynamic-update-slice per leaf, which XLA does in place on a carried
    buffer."""
    return jax.tree.map(
        lambda s, n: jax.lax.dynamic_update_slice(
            s, n[None, None], (g, r) + (0,) * (s.ndim - 2)
        ),
        stack,
        new,
    )


def _keep_rows(keep: Optional[Array], new, old):
    """``new`` where ``keep`` (``[b]`` bool) is True, ``old`` elsewhere —
    every leaf of a layer's cache carries the batch axis in front."""
    if keep is None:
        return new
    return jax.tree.map(
        lambda n, o: jnp.where(keep.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
        new,
        old,
    )


def lm_decode_step(
    params, token_t: Array, caches, pos, cfg: ModelConfig,
    keep: Optional[Array] = None,
) -> Tuple[Array, Any]:
    """One decode step.  token_t: [b] int32; pos: scalar or [b] int32
    (0-based position of this token — a vector gives every batch row /
    serving slot its own position).  ``keep`` (optional ``[b]`` bool)
    limits the cache update to the rows where it is True: the other rows'
    caches come back bit for bit as they went in.  Returns (logits
    [b, vocab], new caches).

    The stacked group caches travel through the layer scans as carry and
    each layer updates its own slice in place, so a step moves each
    layer's state once and never copies the whole stack."""
    dtype = jnp.dtype(cfg.dtype)
    x_t = embed_apply(params["embed"], token_t, dtype)
    if cfg.embed_scale:
        x_t = x_t * jnp.asarray(cfg.d_model**0.5, dtype)
    if cfg.pos == "learned":
        # scalar pos -> [d] broadcast over batch; [b] pos -> [b, d].
        x_t = x_t + jnp.take(params["pos_embed"], pos, axis=0).astype(dtype)
    elif cfg.pos == "sinusoidal":
        x_t = x_t + sinusoidal_pos(jnp.atleast_1d(pos), cfg.d_model).astype(dtype)
    blocks = params["blocks"]
    shared = blocks.get("shared")
    kv_src = caches.get("kv_src")

    runs = _cfg_runs(cfg)

    def group_body(carry, xs):
        x_t, group_caches = carry
        group_params, g = xs
        new_caches = []
        for j, (kind, rcfg, rl) in enumerate(runs):
            def run_body(carry, step_xs, kind=kind, rcfg=rcfg):
                x_t, stack = carry
                p, r = step_xs
                c = _layer_cache(stack, g, r)
                x_t, new = block_decode(
                    shared if kind == "shared_attn" else p, kind, x_t, c, rcfg, pos
                )
                stack = _set_layer_cache(stack, _keep_rows(keep, new, c), g, r)
                return (x_t, stack), None

            rp = None if kind == "shared_attn" else group_params[f"r{j}"]
            (x_t, stack), _ = jax.lax.scan(
                run_body, (x_t, group_caches[j]),
                (rp, jnp.arange(rl, dtype=jnp.int32)), length=rl,
            )
            new_caches.append(stack)
        return (x_t, tuple(new_caches)), None

    if blocks["group"]:
        (x_t, group_caches), _ = jax.lax.scan(
            group_body, (x_t, caches["group"]),
            (blocks["group"], jnp.arange(cfg.n_groups, dtype=jnp.int32)),
        )
    else:
        group_caches = ()
    tail_caches = []
    tail_cfg = cfg.layer_cfg(cfg.attention)
    for i, kind in enumerate(cfg.tail):
        p = shared if kind == "shared_attn" else blocks["tail"][f"t{i}"]
        c = caches["tail"][i]
        x_t, new = block_decode(p, kind, x_t, c, tail_cfg, pos)
        tail_caches.append(_keep_rows(keep, new, c))
    logits = _logits(params, x_t[:, None, :], cfg)[:, 0, :]
    new = {"group": group_caches, "tail": tuple(tail_caches), "kv_src": kv_src}
    return logits, new


# ---------------------------------------------------------------------------
# Cache construction without a prefill pass (dry-run / serving allocation)
# ---------------------------------------------------------------------------


def lm_init_caches(
    cfg: ModelConfig, batch: int, n_max: int, dtype=jnp.bfloat16
):
    """Zero-initialised decode caches with the exact pytree structure that
    lm_prefill produces (group caches stacked over n_groups).  Cache kinds
    resolve through the backend registry PER RUN (each run's backend via
    ``attention_schedule``; ``state_kind`` decides KV vs moment vs SSM
    leaves — a hybrid schedule yields a heterogeneous pytree with mixed
    node types across runs)."""
    from repro.backends import CrossCache, get_backend, resolve_backend  # noqa: PLC0415

    def one(kind, rcfg):
        if kind == "mamba":
            return get_backend("ssm").init_cache(rcfg, batch, n_max, dtype)
        backend = resolve_backend(rcfg)
        self_cache = backend.init_cache(rcfg, batch, n_max, dtype)
        if kind != "cross":
            return self_cache
        n_src = cfg.n_image_tokens if cfg.family == "vlm" else cfg.n_audio_ctx
        cc = CrossCache(kv=backend.init_cross_cache(rcfg, batch, n_src, dtype))
        return (self_cache, cc)

    def stack(tree, rl):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x[None, None], (cfg.n_groups, rl) + x.shape
            ),
            tree,
        )

    group = (
        tuple(stack(one(kind, rcfg), rl) for kind, rcfg, rl in _cfg_runs(cfg))
        if cfg.n_groups
        else ()
    )
    tail_cfg = cfg.layer_cfg(cfg.attention)
    tail = tuple(one(k, tail_cfg) for k in cfg.tail)
    kv_src = None
    if cfg.family == "vlm":
        kv_src = jnp.zeros((batch, cfg.n_image_tokens, cfg.d_model), dtype)
    elif cfg.family == "encdec":
        kv_src = jnp.zeros((batch, cfg.n_audio_ctx, cfg.d_model), dtype)
    return {"group": group, "tail": tail, "kv_src": kv_src}


def lm_state_bytes(cfg: ModelConfig, batch: int, n_max: int,
                   dtype=jnp.bfloat16) -> int:
    """Decode-state bytes of the full cache pytree, summed PER LAYER.

    Shape-only (``jax.eval_shape`` — no allocation), so it prices
    arbitrary configs.  Under a hybrid ``attention_schedule`` each run
    contributes its own backend's state (taylor moments O(1), softmax KV
    O(n_max), a softmax_window ring O(window)), which is what the dryrun
    memory model and the serve admission maths must sum — a single-backend
    estimate is wrong in either direction for hybrids.

    Args:
      cfg: model config.
      batch: batch rows (slots for serving estimates).
      n_max: per-slot token capacity for KV-kind layers.
      dtype: cache dtype.

    Returns:
      Total cache bytes (int).
    """
    shapes = jax.eval_shape(lambda: lm_init_caches(cfg, batch, n_max, dtype))
    return sum(
        int(x.size) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(shapes)
    )
