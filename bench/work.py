"""Required work from shapes: operations and bytes that the algorithm needs,
and the chip's peaks they are divided by.

Counts are made at the model's own widths, never at a kernel's padded
ones, so a roofline share reads the same work whatever implements it.
Peaks come from ``bench/peaks.json`` and from nowhere else; a device kind
that is not in that table is an error.

Order-2 Taylor attention (``1 + s + s²/2`` with ``s = q·k/(α√d)``) is
counted in its chunked form at a fixed chunk length ``TAYLOR_CHUNK``:

* intra-chunk scores: the causal pairs of each chunk, each a ``d``-long
  dot product, the polynomial, and a ``d_v + 1``-long accumulation
  (numerator and denominator);
* inter-chunk moment reads: per query token and query head, the features
  ``φ(q) = [1, q, sym(q⊗q)]`` (``D = 1 + d + d(d+1)/2``, the symmetric
  second moment) contracted with the ``D × (d_v + 1)`` state;
* state update: per key token and kv head, ``φ(k) ⊗ [v, 1]`` added to the
  state.

The first chunk reads no state and the last chunk's update is read by no
one, so both count over ``n - C`` tokens.  The backward pass needs twice
the forward's operations, the rule for contractions.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")
TAYLOR_CHUNK = 128
BF16 = 2
F32 = 4


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Widths:
    """The shapes of a decoder that the counts need."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool = False
    tied: bool = True

    @classmethod
    def of(cls, cfg) -> "Widths":
        """From a ``ModelConfig`` (or anything with its field names)."""
        return cls(
            layers=cfg.n_layers, d_model=cfg.d_model, heads=cfg.n_heads,
            kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
            d_ff=cfg.d_ff, vocab=cfg.vocab, qkv_bias=cfg.qkv_bias,
            tied=cfg.tie_embeddings,
        )


def param_count(w: Widths) -> int:
    """Every parameter; a tied embedding table is counted once."""
    d, h, hk, hd = w.d_model, w.heads, w.kv_heads, w.head_dim
    attn = d * h * hd + 2 * d * hk * hd + h * hd * d
    if w.qkv_bias:
        attn += h * hd + 2 * hk * hd
    layer = attn + 3 * d * w.d_ff + 2 * d
    table = w.vocab * d * (1 if w.tied else 2)
    return w.layers * layer + table + d


def features(d: int) -> int:
    """``D``: length of ``φ(x) = [1, x, sym(x⊗x)]``."""
    return 1 + d + d * (d + 1) // 2


def taylor_token_flops(w: Widths) -> int:
    """One token's moment read (every query head) and state update (every
    kv head) in one layer: the recurrent form's per-token work."""
    d = dv = w.head_dim
    per_head = 2 * features(d) * (dv + 1) + d * (d + 1) // 2
    return (w.heads + w.kv_heads) * per_head


def taylor_fwd_flops(w: Widths, n: int, chunk: int = TAYLOR_CHUNK) -> int:
    """Forward operations of order-2 Taylor attention over one sequence of
    ``n`` tokens in one layer."""
    d = dv = w.head_dim
    c = min(chunk, n)
    chunks = -(-n // c)
    pairs = c * (c + 1) // 2
    intra = w.heads * chunks * pairs * (2 * d + 3 + 2 * (dv + 1))
    return intra + (n - c) * taylor_token_flops(w)


def taylor_fwd_bytes(w: Widths, n: int) -> int:
    """Bytes the forward must move for one sequence in one layer: q, k, v
    read and the output written once, in bf16."""
    d = dv = w.head_dim
    return n * BF16 * (w.heads * d + w.kv_heads * (d + dv) + w.heads * dv)


def taylor_bwd_flops(w: Widths, n: int, chunk: int = TAYLOR_CHUNK) -> int:
    return 2 * taylor_fwd_flops(w, n, chunk)


def taylor_bwd_bytes(w: Widths, n: int) -> int:
    """q, k, v, the output and its gradient read; dq, dk, dv written."""
    d = dv = w.head_dim
    read = w.heads * d + w.kv_heads * (d + dv) + 2 * w.heads * dv
    write = w.heads * d + w.kv_heads * (d + dv)
    return n * BF16 * (read + write)


def fwd_flops_per_token(w: Widths) -> int:
    """Forward operations of one token through the whole model: two per
    parameter (the tied table counted once, as the output head) plus the
    Taylor moment read and update of every layer."""
    return 2 * param_count(w) + w.layers * taylor_token_flops(w)


def train_step_flops(w: Widths, batch: int, seq: int) -> int:
    """Required operations of one training step: ``6·N`` per token plus
    three times the Taylor forward (forward and its twice-as-large
    backward).  Recomputation does not count."""
    tokens = batch * seq
    taylor = w.layers * batch * taylor_fwd_flops(w, seq)
    return 6 * param_count(w) * tokens + 3 * taylor


def state_bytes_per_slot(w: Widths) -> int:
    """Dense fp32 moment state of one sequence: per layer and kv head
    ``n0 [1], s0 [dv], z1 [d], s1 [d, dv], z2 [d, d], s2 [d, d, dv]``."""
    d = dv = w.head_dim
    per_head = 1 + dv + d + d * dv + d * d + d * d * dv
    return w.layers * w.kv_heads * per_head * F32


def decode_step_bytes(w: Widths, active_slots: int) -> int:
    """Bytes one decode step must move: the bf16 weights once, and each
    active slot's dense fp32 state read once and written once."""
    return param_count(w) * BF16 + active_slots * 2 * state_bytes_per_slot(w)


def roofline_share(flops: float, nbytes: float, seconds: float, peak: dict):
    """``(share in %, bound)``: the least time the chip could take, the
    larger of operations over peak FLOP/s and bytes over peak bytes/s,
    over the time measured.  ``bound`` is ``"flops"`` or ``"bytes"``."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
