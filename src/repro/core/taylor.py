"""Higher-order (Taylor) linear attention — the paper's core contribution.

Implements ``softmax(QKᵀ/(α√d))V`` approximated with the order-2 Taylor
expansion of exp, re-associated for linear complexity (paper eq. (2)-(3)).

Three exact-equivalent execution modes (tested against each other):

  * ``parallel``  — materialises the n×n polynomial score matrix.  O(n²d).
    Reference semantics; used for short sequences and tests.
  * ``chunked``   — the TPU-native form: the sequence is processed in chunks
    of C tokens; intra-chunk attention is quadratic on a C×C tile (MXU
    friendly) and inter-chunk information flows through constant-size moment
    state (S0, S1, S2, z*).  O(n·d²·d_v / C + n·C·d).  This is the form the
    Pallas kernel (src/repro/kernels/taylor_attention) accelerates.
  * ``recurrent`` — token-level RNN; the decode path.  O(1) state per step.

All modes support GQA: q is [b, h, n, d]; k, v are [b, h_kv, n, d] with
``h % h_kv == 0``.  The moment state depends only on K/V and is therefore
**per kv-head** — with MQA (h_kv=1) a single state serves all query heads.

State size per kv head is ``(1 + d + d²)·d_v`` — constant in sequence length,
which beats a KV cache (2·n·d) for any context n > d·d_v/2 (≈8k for d=128).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.feature_map import (
    TaylorConfig,
    layernorm_no_affine,
    poly_scores,
)
from repro.distributed.api import constrain

Array = jax.Array


class TaylorState(NamedTuple):
    """Running moments of the Taylor-linear attention (per batch, kv-head).

    Shapes (b = batch, k = kv heads, d = qk head dim, v = value head dim):
      n0: [b, k]           token count (denominator constant term)
      s0: [b, k, v]        Σ_j v_j                    (numerator constant term)
      z1: [b, k, d]        Σ_j k_j                    (denominator linear term)
      s1: [b, k, d, v]     Σ_j k_j ⊗ v_j              (numerator linear term)
      z2: [b, k, d, d]     Σ_j k_j ⊗ k_j              (denominator quadratic)
      s2: [b, k, d, d, v]  Σ_j k_j ⊗ k_j ⊗ v_j        (numerator quadratic)

    z2/s2 are ``None`` for order-1 configs.
    """

    n0: Array
    s0: Array
    z1: Array
    s1: Array
    z2: Optional[Array]
    s2: Optional[Array]


def init_taylor_state(
    batch: int,
    kv_heads: int,
    d: int,
    d_v: int,
    cfg: TaylorConfig,
    dtype=jnp.float32,
) -> TaylorState:
    """Zero state for prefill/decode.

    With ``cfg.sym_state`` the second moments use the exact symmetric
    compression: [d(d+1)/2(, d_v)] instead of [d, d(, d_v)] — half the
    decode-state bytes (the property that lets gemma-7b's d=256 heads fit
    a 16 GB chip at decode; see EXPERIMENTS.md §Perf).

    Under a sharding-rules context the moment tensors are annotated
    (batch over dp, remaining dims left to the partitioner) so the scan
    carries don't silently replicate 4 GB second moments per device."""
    z = lambda *s: jnp.zeros(s, dtype)
    second = cfg.order >= 2
    free = lambda x: constrain(x, "dp", *(["*"] * (x.ndim - 1)))
    if cfg.sym_state:
        d2 = (d * (d + 1)) // 2
        z2 = free(z(batch, kv_heads, d2)) if second else None
        s2 = free(z(batch, kv_heads, d2, d_v)) if second else None
    else:
        z2 = free(z(batch, kv_heads, d, d)) if second else None
        s2 = free(z(batch, kv_heads, d, d, d_v)) if second else None
    return TaylorState(
        n0=free(z(batch, kv_heads)),
        s0=free(z(batch, kv_heads, d_v)),
        z1=free(z(batch, kv_heads, d)),
        s1=free(z(batch, kv_heads, d, d_v)),
        z2=z2,
        s2=s2,
    )


def _norm_qk(q: Array, k: Array, cfg: TaylorConfig):
    if cfg.normalize_qk:
        q = layernorm_no_affine(q).astype(q.dtype)
        k = layernorm_no_affine(k).astype(k.dtype)
    return q, k


def _group(q: Array, h_kv: int) -> Array:
    """[b, h, n, d] -> [b, h_kv, g, n, d]."""
    b, h, n, d = q.shape
    assert h % h_kv == 0, f"q heads {h} not divisible by kv heads {h_kv}"
    return q.reshape(b, h_kv, h // h_kv, n, d)


def _ungroup(o: Array) -> Array:
    """[b, h_kv, g, n, v] -> [b, h, n, v]."""
    b, hk, g, n, v = o.shape
    return o.reshape(b, hk * g, n, v)


def _safe_div(num: Array, den: Array, eps: float = 1e-6) -> Array:
    den = den.astype(jnp.float32)
    den = jnp.where(jnp.abs(den) < eps, jnp.where(den < 0, -eps, eps), den)
    return num / den[..., None]


def decay_gammas(h_kv: int, decay: float) -> Array:
    """Per-kv-head decay rates from the single ``TaylorConfig.decay`` scalar.

    Geometric spread ``γ_h = decay^((h+1)/h_kv)`` for ``h = 0..h_kv-1``
    (ALiBi-slope style): the last head decays at exactly ``decay``, earlier
    heads progressively slower, so one scalar yields a bank of effective
    context lengths.  With ``h_kv == 1`` this is just ``[decay]``.

    Args:
      h_kv: number of kv heads.
      decay: the config scalar in (0, 1].

    Returns:
      ``[h_kv]`` f32 array of per-head rates.
    """
    h = jnp.arange(1, h_kv + 1, dtype=jnp.float32)
    return jnp.asarray(decay, jnp.float32) ** (h / h_kv)


# ---------------------------------------------------------------------------
# Parallel (quadratic) reference mode.
# ---------------------------------------------------------------------------


def taylor_attention_parallel(
    q: Array, k: Array, v: Array, cfg: TaylorConfig, causal: bool = True
) -> Array:
    """Reference O(n²) evaluation of the Taylor-approximated attention."""
    b, h, n, d = q.shape
    h_kv = k.shape[1]
    q, k = _norm_qk(q, k, cfg)
    qg = _group(q, h_kv)
    a = cfg.scale(d)
    s = jnp.einsum(
        "bkgid,bkjd->bkgij", qg, k, preferred_element_type=jnp.float32
    ) * a
    p = poly_scores(s, cfg)
    if causal:
        mask = jnp.tril(jnp.ones((n, n), dtype=bool))
        p = jnp.where(mask, p, 0.0)
    if cfg.decay != 1.0:
        if not causal:
            raise ValueError("taylor decay is causal-self-attention only")
        g_h = decay_gammas(h_kv, cfg.decay)  # [hk]
        delta = (
            jnp.arange(n, dtype=jnp.float32)[:, None]
            - jnp.arange(n, dtype=jnp.float32)[None, :]
        )
        # clamp j>i to 0 — those entries are already masked, and γ^(i-j)
        # would overflow there for small γ
        w = g_h[:, None, None] ** jnp.maximum(delta, 0.0)  # [hk, n, n]
        p = p * w[None, :, None]
    num = jnp.einsum("bkgij,bkjv->bkgiv", p, v, preferred_element_type=jnp.float32)
    den = jnp.sum(p, axis=-1)
    return _ungroup(_safe_div(num, den)).astype(v.dtype)


# ---------------------------------------------------------------------------
# Chunked mode (the TPU-native paper implementation).
# ---------------------------------------------------------------------------


_QUAD_TILE = 32  # first-axis tile of S2 contractions (bounds transients)


def _quad_num(qg: Array, s2: Array, half_a2: float) -> Array:
    """(a²/2)·(q ⊗ q)·S2 without materialising a [*, c, d, d_v] temp.

    Tiles the first moment axis (same scheme as the Pallas kernel): per tile
    the transient is [*, c, T·d] instead of [*, c, d, d_v] — 4-16× smaller,
    which is what keeps the XLA path inside HBM for d=128..256 heads.
    """
    f32 = jnp.float32
    b, hk, d, _, dv = s2.shape
    t = _QUAD_TILE if d % _QUAD_TILE == 0 else d
    acc = None
    for t0 in range(0, d, t):
        qq = (qg[..., t0 : t0 + t, None] * qg[..., None, :]).reshape(
            qg.shape[:-1] + (t * d,)
        )
        s2t = s2[:, :, t0 : t0 + t].reshape(b, hk, t * d, dv)
        part = jnp.einsum("bkgcf,bkfv->bkgcv", qq, s2t, preferred_element_type=f32)
        acc = part if acc is None else acc + part
    return half_a2 * acc


def _chunk_inter(qg: Array, state: TaylorState, cfg: TaylorConfig, a: float):
    """Contribution of all previous chunks to (num, den) for query block qg.

    qg: [b, k, g, c, d].  Returns num [b,k,g,c,v], den [b,k,g,c].
    Uses the full (d×d) second moment — the symvec compression is a kernel-
    level optimisation; mathematically identical.
    """
    c0 = 0.0 if cfg.minus_one else 1.0
    f32 = jnp.float32
    num = a * jnp.einsum("bkgcd,bkdv->bkgcv", qg, state.s1, preferred_element_type=f32)
    den = a * jnp.einsum("bkgcd,bkd->bkgc", qg, state.z1, preferred_element_type=f32)
    if c0:
        num = num + state.s0[:, :, None, None, :]
        den = den + state.n0[:, :, None, None]
    if cfg.order >= 2:
        half_a2 = 0.5 * a * a
        if cfg.sym_state:
            from repro.core.feature_map import symvec  # noqa: PLC0415

            phi2 = symvec(qg.astype(f32))  # [b,k,g,c,D2]; phi2(q)·phi2(k) = (q·k)²
            num = num + half_a2 * jnp.einsum(
                "bkgcf,bkfv->bkgcv", phi2, state.s2, preferred_element_type=f32
            )
            den = den + half_a2 * jnp.einsum(
                "bkgcf,bkf->bkgc", phi2, state.z2, preferred_element_type=f32
            )
        else:
            if state.s2 is not None:  # None: the decode kernel reads S2
                num = num + _quad_num(qg, state.s2, half_a2)
            u = jnp.einsum(
                "bkgcd,bkde->bkgce", qg, state.z2, preferred_element_type=f32
            )
            den = den + half_a2 * jnp.einsum(
                "bkgce,bkgce->bkgc", qg, u, preferred_element_type=f32
            )
    return num, den


def _state_update(state: TaylorState, kc: Array, vc: Array, cfg: TaylorConfig) -> TaylorState:
    """Accumulate one chunk of keys/values into the moment state.

    kc: [b, k, c, d], vc: [b, k, c, v].

    With ``cfg.decay != 1.0`` the prefix sums become decayed sums: the old
    state is carried with ``γ^c`` and token j (local, 0-based) enters with
    weight ``γ^(c-1-j)``, so the result is always the state *as of the last
    absorbed token*.  Each weight is applied exactly ONCE per moment
    (folded into v for s0/s1/s2, into k for z1, into the k⊗k product for
    z2).  The ``decay == 1.0`` branch is the original code path untouched —
    bit-identical by construction.
    """
    f32 = jnp.float32
    kc32 = kc.astype(f32)
    vc32 = vc.astype(f32)
    c = kc.shape[2]
    if cfg.decay != 1.0:
        g_h = decay_gammas(kc.shape[1], cfg.decay)  # [hk]
        w = g_h[:, None] ** jnp.arange(c - 1, -1, -1, dtype=f32)[None, :]  # [hk,c]
        carry = (g_h**c)[None, :]  # [1, hk]
        vw = vc32 * w[None, :, :, None]
        kw = kc32 * w[None, :, :, None]
        tok = jnp.sum(w, axis=1)[None, :]
        old = lambda x, nd: x * carry.reshape(carry.shape + (1,) * nd)
    else:
        vw, kw, tok = vc32, kc32, c
        old = lambda x, nd: x
    n0 = old(state.n0, 0) + tok
    s0 = old(state.s0, 1) + jnp.sum(vw, axis=2)
    z1 = old(state.z1, 1) + jnp.sum(kw, axis=2)
    s1 = old(state.s1, 2) + jnp.einsum("bkcd,bkcv->bkdv", kc32, vw)
    z2, s2 = state.z2, state.s2
    if cfg.order >= 2 and cfg.sym_state:
        from repro.core.feature_map import symvec  # noqa: PLC0415

        phi2 = symvec(kc32)  # [b,k,c,D2]
        phi2w = phi2 if cfg.decay == 1.0 else phi2 * w[None, :, :, None]
        z2 = old(state.z2, 1) + jnp.sum(phi2w, axis=2)
        s2 = old(state.s2, 2) + jnp.einsum("bkcf,bkcv->bkfv", phi2, vw)
    elif cfg.order >= 2:
        z2 = old(state.z2, 2) + jnp.einsum("bkcd,bkce->bkde", kw, kc32)
        if state.s2 is not None:  # None: the decode kernel updates S2
            # d-tiled: a direct 3-operand einsum materialises [b,k,c,d,e]
            # (13 GB for a 1600-token cross-attention source at d=128)
            b, hk, c, d = kc.shape
            t = _QUAD_TILE if d % _QUAD_TILE == 0 else d
            parts = []
            for t0 in range(0, d, t):
                kk = (kc32[..., t0 : t0 + t, None] * kc32[..., None, :]).reshape(
                    b, hk, c, t * d
                )
                parts.append(
                    jnp.einsum("bkcf,bkcv->bkfv", kk, vw).reshape(
                        b, hk, t, d, vc.shape[-1]
                    )
                )
            s2 = old(state.s2, 3) + jnp.concatenate(parts, axis=2)
    return TaylorState(n0=n0, s0=s0, z1=z1, s1=s1, z2=z2, s2=s2)


def taylor_attention_chunked(
    q: Array,
    k: Array,
    v: Array,
    cfg: TaylorConfig,
    chunk: int = 128,
    initial_state: Optional[TaylorState] = None,
    return_state: bool = False,
):
    """Causal Taylor linear attention via chunk-level scan (exact).

    Sequence length must be padded to a multiple of ``chunk`` by the caller
    (models do this; ops.py handles it for the Pallas kernel).

    The plain-training path (no initial/returned state) routes through a
    custom VJP (core/taylor_vjp.py) that recomputes moment states in the
    backward pass instead of letting scan autodiff save them per chunk —
    O(n·d) residuals instead of O(n/C · d²·d_v).

    Returns out [b, h, n, v] (and the final TaylorState if requested —
    used for prefill→decode handoff and context parallelism).
    """
    b, h, n, d = q.shape
    h_kv = k.shape[1]
    d_v = v.shape[-1]
    if n % chunk != 0:
        raise ValueError(f"seq len {n} not a multiple of chunk {chunk}")
    nc = n // chunk
    q, k = _norm_qk(q, k, cfg)
    a = cfg.scale(d)
    qg = _group(q, h_kv)  # [b, hk, g, n, d]
    g = qg.shape[2]

    if (
        initial_state is None
        and not return_state
        and not cfg.sym_state
        and cfg.decay == 1.0
    ):
        # (the custom VJP's tiled backward is written for the full second
        # moment; sym_state is a decode/serving optimisation and decayed
        # states fall back to scan autodiff)
        from repro.core.taylor_vjp import taylor_chunked_core  # noqa: PLC0415

        out = taylor_chunked_core(qg, k, v, cfg, chunk)
        return _ungroup(out).astype(v.dtype)

    # chunk-major layout for the scan: [nc, b, hk, (g,) c, ...].  Pin the
    # sharding: batch over dp, heads over tp (kv-heads first, else groups),
    # and crucially the CHUNK dim replicated — scan slices along it, and a
    # sharded scan axis forces SPMD into full rematerialisation.
    qs = jnp.moveaxis(qg.reshape(b, h_kv, g, nc, chunk, d), 3, 0)
    ks = jnp.moveaxis(k.reshape(b, h_kv, nc, chunk, d), 2, 0)
    vs = jnp.moveaxis(v.reshape(b, h_kv, nc, chunk, d_v), 2, 0)
    qs = constrain(qs, None, "dp", "*", "*", "*", "*")
    ks = constrain(ks, None, "dp", "*", "*", "*")
    vs = constrain(vs, None, "dp", "*", "*", "*")

    state0 = initial_state
    if state0 is None:
        state0 = init_taylor_state(b, h_kv, d, d_v, cfg)

    nums, dens, final_state = chunked_num_den(qs, ks, vs, cfg, state0)
    # [nc, b, hk, g, c, v] -> [b, hk, g, n, v]
    nums = jnp.moveaxis(nums, 0, 3).reshape(b, h_kv, g, n, d_v)
    dens = jnp.moveaxis(dens, 0, 3).reshape(b, h_kv, g, n)
    out = _ungroup(_safe_div(nums, dens)).astype(v.dtype)
    if return_state:
        return out, final_state
    return out


def chunked_num_den(qs, ks, vs, cfg: TaylorConfig, state0: TaylorState):
    """Scan over chunk-major (qs [nc,b,hk,g,c,d]; ks/vs [nc,b,hk,c,·]).
    Returns unnormalised (nums, dens, final_state) — used by the chunked
    entry point and by context parallelism (core/context_parallel.py)."""
    chunk = qs.shape[4]
    d = qs.shape[-1]
    a = cfg.scale(d)
    mask = jnp.tril(jnp.ones((chunk, chunk), dtype=bool))
    f32 = jnp.float32
    if cfg.decay != 1.0:
        # intra-chunk pair weight γ^(i-j); inter-chunk scale γ^(i+1) lifts
        # the carried state (as-of the previous chunk's LAST token) to each
        # local query position i.
        g_h = decay_gammas(qs.shape[2], cfg.decay)  # [hk]
        delta = (
            jnp.arange(chunk, dtype=f32)[:, None] - jnp.arange(chunk, dtype=f32)[None, :]
        )
        w_intra = g_h[:, None, None] ** jnp.maximum(delta, 0.0)  # [hk, c, c]
        w_inter = g_h[:, None] ** jnp.arange(1, chunk + 1, dtype=f32)[None, :]  # [hk, c]

    def step(state, xs):
        qc, kc, vc = xs
        s = jnp.einsum(
            "bkgid,bkjd->bkgij", qc, kc, preferred_element_type=jnp.float32
        ) * a
        p = jnp.where(mask, poly_scores(s, cfg), 0.0)
        if cfg.decay != 1.0:
            p = p * w_intra[None, :, None]
        num = jnp.einsum("bkgij,bkjv->bkgiv", p, vc, preferred_element_type=jnp.float32)
        den = jnp.sum(p, axis=-1)
        inum, iden = _chunk_inter(qc, state, cfg, a)
        if cfg.decay != 1.0:
            inum = inum * w_inter[None, :, None, :, None]
            iden = iden * w_inter[None, :, None, :]
        new_state = _state_update(state, kc, vc, cfg)
        return new_state, (num + inum, den + iden)

    final_state, (nums, dens) = jax.lax.scan(step, state0, (qs, ks, vs))
    return nums, dens, final_state


# ---------------------------------------------------------------------------
# Non-causal / cross-attention mode: one global state, single pass.
# ---------------------------------------------------------------------------


def taylor_attention_noncausal(
    q: Array, k: Array, v: Array, cfg: TaylorConfig, chunk: int = 128
) -> Array:
    """Encoder / cross-attention: every query sees every key.

    O(n·d²·d_v) with a single global moment state.  Queries are processed in
    chunks under a remat'd scan: contracting all nq queries against S2 at
    once materialises an [b,hk,g,nq,T·d] transient (tens of GB at nq=4k) —
    chunking bounds it to one chunk's worth.
    q: [b, h, nq, d]; k, v: [b, h_kv, nk, d/v].
    """
    b, h, nq, d = q.shape
    h_kv = k.shape[1]
    d_v = v.shape[-1]
    if cfg.decay != 1.0:
        raise ValueError(
            "taylor decay is causal-self-attention only (a position-decayed "
            "global source state is ill-defined)"
        )
    q, k = _norm_qk(q, k, cfg)
    a = cfg.scale(d)
    qg = _group(q, h_kv)  # [b, hk, g, nq, d]
    g = qg.shape[2]
    state = init_taylor_state(b, h_kv, d, d_v, cfg)
    state = _state_update(state, k, v, cfg)
    if nq % chunk != 0 or nq <= chunk:
        num, den = _chunk_inter(qg, state, cfg, a)
        return _ungroup(_safe_div(num, den)).astype(v.dtype)

    ncq = nq // chunk
    qs = jnp.moveaxis(qg.reshape(b, h_kv, g, ncq, chunk, d), 3, 0)
    qs = constrain(qs, None, "dp", "*", "*", "*", "*")

    def qstep(_, qc):
        num, den = _chunk_inter(qc, state, cfg, a)
        return None, _safe_div(num, den)

    _, outs = jax.lax.scan(jax.checkpoint(qstep), None, qs)
    out = jnp.moveaxis(outs, 0, 3).reshape(b, h_kv, g, nq, d_v)
    return _ungroup(out).astype(v.dtype)


# ---------------------------------------------------------------------------
# Recurrent mode — decoding.
# ---------------------------------------------------------------------------


def taylor_decode_step(
    state: TaylorState,
    q_t: Array,
    k_t: Array,
    v_t: Array,
    cfg: TaylorConfig,
    s2_pass=None,
):
    """One autoregressive step.

    q_t: [b, h, d]; k_t: [b, h_kv, d]; v_t: [b, h_kv, v].
    Returns (out_t [b, h, v], new_state).  The new token attends to itself,
    so the state is updated *before* the read (inclusive causal semantics).

    ``s2_pass`` (optional), ``(qg [b, h_kv, g, d], k_t, v_t) -> (num2
    [b, h_kv, g, v], s2)``, takes over the second moment: it adds the
    token to S2 and returns ``(q ⊗ q)·S2`` of the new S2 in one pass (the
    Pallas decode kernel); ``state.s2`` is then not read here, and the
    returned state carries the ``s2`` it gave back.
    """
    b, h, d = q_t.shape
    h_kv = k_t.shape[1]
    if cfg.normalize_qk:
        q_t = layernorm_no_affine(q_t).astype(q_t.dtype)
        k_t = layernorm_no_affine(k_t).astype(k_t.dtype)
    qg = q_t.reshape(b, h_kv, h // h_kv, 1, d)
    if s2_pass is not None:
        num2, s2 = s2_pass(qg[:, :, :, 0], k_t, v_t)
        state = state._replace(s2=None)
    state = _state_update(state, k_t[:, :, None, :], v_t[:, :, None, :], cfg)
    a = cfg.scale(d)
    num, den = _chunk_inter(qg, state, cfg, a)
    if s2_pass is not None:
        num = num + 0.5 * a * a * num2[:, :, :, None, :]
        state = state._replace(s2=s2)
    out = _safe_div(num, den)[:, :, :, 0, :]  # [b, hk, g, v]
    return out.reshape(b, h, v_t.shape[-1]).astype(v_t.dtype), state


def taylor_attention_recurrent(
    q: Array, k: Array, v: Array, cfg: TaylorConfig
) -> Array:
    """Token-level RNN evaluation (test oracle for the decode path)."""
    b, h, n, d = q.shape
    h_kv = k.shape[1]
    q, k = _norm_qk(q, k, cfg)
    # normalisation already applied: use a cfg copy that skips it per-step.
    import dataclasses

    step_cfg = dataclasses.replace(cfg, normalize_qk=False)
    state0 = init_taylor_state(b, h_kv, d, v.shape[-1], cfg)

    def step(state, xs):
        q_t, k_t, v_t = xs
        out_t, state = taylor_decode_step(state, q_t, k_t, v_t, step_cfg)
        return state, out_t

    xs = (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0))
    _, outs = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(outs, 0, 2)  # [b, h, n, v]


# ---------------------------------------------------------------------------
# Public state helpers: build / read a moment state without the scan.
# (The backend layer and cross-attention use these — no private imports.)
# ---------------------------------------------------------------------------


def taylor_prefill_state(
    k: Array, v: Array, cfg: TaylorConfig, state: Optional[TaylorState] = None
) -> TaylorState:
    """Moment state of a key/value sequence in one shot (no output pass).

    The state every query AFTER the sequence reads: used for short-prompt
    prefill→decode handoff (where the chunked scan's ``return_state`` does
    not apply) and for cross-attention sources (encoder output / vision
    tokens), whose state is global and query-independent.

    Args:
      k: keys ``[b, hk, n, d]`` (normalised internally per
        ``cfg.normalize_qk`` — pass RAW projections).
      v: values ``[b, hk, n, d_v]``.
      cfg: TaylorConfig.
      state: optional state to accumulate onto (defaults to zeros).

    Returns:
      ``TaylorState`` with the whole sequence absorbed.
    """
    _, kn = _norm_qk(k, k, cfg)
    if state is None:
        state = init_taylor_state(
            k.shape[0], k.shape[1], k.shape[-1], v.shape[-1], cfg
        )
    return _state_update(state, kn, v, cfg)


def taylor_state_read(state: TaylorState, q_t: Array, cfg: TaylorConfig) -> Array:
    """Read one token's attention output from a FIXED moment state.

    The read half of ``taylor_decode_step`` (no state update) — the
    cross-attention decode path, where the source state never changes.

    Args:
      state: the moment state (per batch row and kv head).
      q_t: queries ``[b, h, d]`` (normalised internally per
        ``cfg.normalize_qk``).
      cfg: TaylorConfig.

    Returns:
      Attention output ``[b, h, d_v]`` (f32).
    """
    b, h, d = q_t.shape
    hk = state.z1.shape[1]
    if cfg.normalize_qk:
        q_t = layernorm_no_affine(q_t).astype(q_t.dtype)
    qg = q_t.reshape(b, hk, h // hk, 1, d)
    num, den = _chunk_inter(qg, state, cfg, cfg.scale(d))
    return _safe_div(num, den)[:, :, :, 0, :].reshape(b, h, -1)


# ---------------------------------------------------------------------------
# Context parallelism helper: merge per-shard states (moments are sums).
# ---------------------------------------------------------------------------


def merge_states(a: TaylorState, b: TaylorState) -> TaylorState:
    """States are prefix sums ⇒ merging two consecutive shards is addition.

    Valid for ``decay == 1.0`` only (a decayed merge would need shard b's
    token count to discount shard a); the backend rejects CP + decay."""
    add = lambda x, y: None if x is None else x + y
    return TaylorState(*(add(x, y) for x, y in zip(a, b)))


def taylor_attention(
    q: Array,
    k: Array,
    v: Array,
    cfg: TaylorConfig,
    causal: bool = True,
    mode: str = "auto",
    chunk: int = 128,
) -> Array:
    """Dispatching entry point.

    mode: "auto" | "parallel" | "chunked" | "recurrent".
    "auto" picks parallel for short sequences and chunked otherwise (and the
    non-causal single-state path when causal=False).
    """
    n = q.shape[2]
    if not causal:
        return taylor_attention_noncausal(q, k, v, cfg)
    if mode == "auto":
        mode = "parallel" if n <= 2 * chunk else "chunked"
    if mode == "parallel":
        return taylor_attention_parallel(q, k, v, cfg, causal=True)
    if mode == "chunked":
        if n % chunk != 0:
            return taylor_attention_parallel(q, k, v, cfg, causal=True)
        return taylor_attention_chunked(q, k, v, cfg, chunk=chunk)
    if mode == "recurrent":
        return taylor_attention_recurrent(q, k, v, cfg)
    raise ValueError(f"unknown mode {mode!r}")
