"""Plain reference of the decoders the benchmark runs, in float32.

A pre-norm decoder as the published configurations describe it (Qwen2,
SmolLM: RMSNorm, rotary positions in the rotate-half form, grouped-query
attention with optional q/k/v biases, a SiLU-gated MLP, a tied output
head), with the attention that this system serves in their place: the
paper's order-2 Taylor attention, written in its O(n²) form,

    s = LN(q)·LN(k) / (α √d),   p = 1 + s + s²/2 (causal),
    out_i = Σ_j p_ij v_j / Σ_j p_ij,

with LN a LayerNorm without affine parameters.  It reads the benchmark's
seeded weights in the layout the system stores them, a nested dict, and
the sizes from the configuration file; it imports nothing of the
program.  There is no cache, no chunking, no kernel and no batching:
one sequence at a time, the whole sequence at once (its queries taken
``ROWS`` at a time, so that the score matrix fits), every matrix product
at ``precision="highest"``.

Departures from the published models: the attention, as said; the
weights are seeded, not trained.  ``dtype`` lets the same code run in a
lower precision, which is what the benchmark's controls do: ``bfloat16``
computes everything in bfloat16; ``FP8`` rounds every weight to float8
(e4m3, one scale per tensor) and computes in bfloat16.

This is the default reference: a configuration file names another module
of this directory under ``"reference"`` where its architecture needs one
(``bench/catalog.py``).  Every reference module gives what the checks
use (``bench/entries``, ``bench/control.py``):

* ``Spec.from_config(doc)``: a hashable spec of the sizes and constants,
  from the configuration file; it raises where the module cannot compute
  the file's architecture;
* ``logits_at(spec, params, tokens, idx, dtype=float32)``: float32 logits
  ``[len(idx), vocab]`` at positions ``idx`` of one sequence (serving);
* ``row_loss_and_grad(spec, params, tokens, labels, dtype=float32)``:
  ``(loss, grads)`` of one sequence, grads float32 (training);
* ``FP8``: the ``dtype`` of the float8 control.

``params`` is the benchmark's seeded tree (``bench/weights.py``) in the
program's layout.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp


FP8 = "fp8"
ROWS = 1024  # queries per block of the O(n²) attention


def _low_weights(x, dtype):
    """A weight as the reference computed in ``dtype`` reads it."""
    if dtype != FP8:
        return x.astype(dtype)
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return ((x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale).astype(jnp.bfloat16)


@dataclasses.dataclass(frozen=True)
class Spec:
    """Sizes and constants of one decoder, from its configuration file."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    alpha: float = 3.0
    ln_eps: float = 1e-6

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads

    @classmethod
    def from_config(cls, doc: dict) -> "Spec":
        m, a = doc["model"], doc["attention"]
        if a["kind"] != "taylor" or a["order"] != 2:
            raise ValueError(f"the reference computes order-2 taylor, not {a}")
        return cls(
            layers=m["num_hidden_layers"], d_model=m["hidden_size"],
            heads=m["num_attention_heads"], kv_heads=m["num_key_value_heads"],
            d_ff=m["intermediate_size"], vocab=m["vocab_size"],
            rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
            alpha=float(a["alpha"]),
        )


def layer_stack(params: Dict[str, Any]) -> Dict[str, Any]:
    """The per-layer weights with one leading layer axis.  The system
    stores them as ``blocks.group.r0`` leaves of shape ``[groups, 1, ...]``
    for a pattern of one block kind."""
    runs = params["blocks"]["group"]
    if set(runs) != {"r0"}:
        raise ValueError(f"expected one run of blocks, got {sorted(runs)}")
    return jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), runs["r0"])


def _mm(*args):
    return jnp.einsum(*args, precision="highest")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def layer_norm(x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    xc = x - mu
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps)


def rope(x, theta):
    """Rotary positions, rotate-half form; x is ``[n, heads, hd]``."""
    n, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def taylor_attention(spec: Spec, q, k, v):
    """O(n²) order-2 Taylor attention; q ``[n, H, hd]``, k, v ``[n, Hk, hd]``.
    Queries go ``ROWS`` at a time, each block against the keys up to its
    last row."""
    n, h, hd = q.shape
    g = h // spec.kv_heads
    q = layer_norm(q, spec.ln_eps).reshape(n, spec.kv_heads, g, hd)
    k = layer_norm(k, spec.ln_eps)
    out = []
    for r0 in range(0, n, ROWS):
        r1 = min(n, r0 + ROWS)
        s = _mm("ikgd,jkd->kgij", q[r0:r1], k[:r1]) / (spec.alpha * hd**0.5)
        p = 1.0 + s + 0.5 * s * s
        causal = jnp.arange(r0, r1)[:, None] >= jnp.arange(r1)[None, :]
        p = jnp.where(causal, p, 0.0)
        num = _mm("kgij,jkd->ikgd", p, v[:r1])
        den = jnp.sum(p, -1)  # [k, g, i]
        out.append(num / jnp.transpose(den, (2, 0, 1))[..., None])
    return jnp.concatenate(out).reshape(n, h, hd)


def block(spec: Spec, x, lp):
    """One decoder layer on ``x [n, d]``."""
    h = rms_norm(x, lp["norm1"]["scale"], spec.norm_eps)
    a = lp["attn"]

    def proj(name):
        y = _mm("nd,dhk->nhk", h, a[name]["w"])
        return y + a[name]["b"] if "b" in a[name] else y

    q = rope(proj("wq"), spec.rope_theta)
    k = rope(proj("wk"), spec.rope_theta)
    o = taylor_attention(spec, q, k, proj("wv"))
    x = x + _mm("nhk,hkd->nd", o, a["wo"]["w"])
    h = rms_norm(x, lp["norm2"]["scale"], spec.norm_eps)
    m = lp["mlp"]
    gate = _mm("nd,df->nf", h, m["w_gate"])
    up = _mm("nd,df->nf", h, m["w_up"])
    return x + _mm("nf,fd->nd", jax.nn.silu(gate) * up, m["w_down"])


def hidden(spec: Spec, params, tokens, dtype=jnp.float32, remat=False):
    """Final-normed hidden states ``[n, d]`` of one sequence."""
    cast = lambda t: jax.tree.map(lambda x: _low_weights(x, dtype), t)
    x = _low_weights(params["embed"]["w"], dtype)[tokens]

    def body(x, lp):
        return block(spec, x, cast(lp)), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, layer_stack(params))
    return rms_norm(x, _low_weights(params["final_norm"]["scale"], dtype), spec.norm_eps)


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def logits_at(spec: Spec, params, tokens, idx, dtype=jnp.float32):
    """Logits ``[len(idx), vocab]`` (float32) at positions ``idx`` of one
    sequence; position ``i`` predicts token ``i + 1``."""
    x = hidden(spec, params, tokens, dtype)[idx]
    out = _mm("nd,vd->nv", x, _low_weights(params["embed"]["w"], dtype))
    return out.astype(jnp.float32)


def row_loss(spec: Spec, params, tokens, labels, dtype=jnp.float32):
    """Mean next-token cross-entropy of one sequence (float32 softmax)."""
    x = hidden(spec, params, tokens, dtype, remat=True)
    logits = _mm("nd,vd->nv", x, _low_weights(params["embed"]["w"], dtype))
    logits = logits.astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def row_loss_and_grad(spec: Spec, params, tokens, labels, dtype=jnp.float32):
    """``(loss, grads)`` of one sequence; grads in float32."""
    return jax.value_and_grad(
        lambda p: row_loss(spec, p, tokens, labels, dtype)
    )(params)
