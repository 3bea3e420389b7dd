"""Quantised Taylor moment state: error vs decode length, machine-asserted.

Worst-case harness: the fp32 reference decode and a run whose state is
quantise→dequantise round-tripped after EVERY token (the serve engine
re-encodes once per decode block, so per-token is strictly harsher).
Pinned constants come from measurement on these exact seeds/configs
(2x headroom over the observed maxima):

* int8 (7-bit mantissa steps of a pow2 scale) — teacher-forced logit
  MAE stays under 0.25 and NO greedy decision whose fp32 top-2 margin
  exceeds 0.2 ever flips, across 32 decode steps, orders 1/2, GQA/MQA.
* fp8 (e4m3, 3-bit mantissa) — MAE under 1.25; decisions with margin
  above 1.5 never flip.  (fp8 trades mantissa for range: it is the
  COARSER format at the reduced models' activation scales, so its
  bounds are wider — the test pins that ordering too.)
* Free-running greedy identity holds to a pinned per-dtype horizon on
  a pinned (arch, order, seed) cell; beyond the horizon only the MAE
  bound applies.  Near-uniform random-init logits make unconditional
  token identity meaningless (margins ~1e-3 flip under ANY
  perturbation), which is why the identity property is margin-gated.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import lm_init
from repro.models.lm import lm_decode_step, lm_init_caches
from repro.serve.state_repr import QuantizedCodec

STEPS = 32
PROMPT = 12
N_MAX = STEPS + PROMPT + 4

# measured maxima over the full grid (see module docstring): MAE 0.086 /
# 0.386, flip margins 0.034 / 0.602 for int8 / fp8 (jax 0.9.0, CPU).
MAE_TOL = {"int8": 0.25, "fp8": 1.25}
MARGIN = {"int8": 0.2, "fp8": 1.5}

# free-running identity horizons, pinned on the cell named below
# (measured: no mismatch within the 32 steps for either dtype).
HORIZON = {"int8": 32, "fp8": 24}
HORIZON_CELL = {"int8": ("qwen2-1.5b", 2, 1), "fp8": ("qwen2-1.5b", 1, 1)}

ARCHS = {"qwen2-1.5b": "GQA", "granite-20b": "MQA"}


@functools.lru_cache(maxsize=None)
def _model(arch, order):
    cfg = get_reduced(arch)
    cfg = cfg.replace(taylor=dataclasses.replace(cfg.taylor, order=order))
    assert cfg.attention == "taylor"
    return cfg, lm_init(jax.random.PRNGKey(0), cfg)


@functools.lru_cache(maxsize=None)
def _steps(cfg):
    @functools.partial(jax.jit, static_argnames=("codec",))
    def step_q(params, tok, caches, pos, codec):
        logits, caches = lm_decode_step(params, tok, caches, pos, cfg)
        return logits, codec.decode(codec.encode(caches))

    @jax.jit
    def step_r(params, tok, caches, pos):
        return lm_decode_step(params, tok, caches, pos, cfg)

    return step_r, step_q


@functools.lru_cache(maxsize=None)
def _run_pair(arch, order, qdtype, seed, teacher_forced):
    """Lockstep fp32 / per-token-quantised decode.

    Returns (maes, flip_margins, first_free_mismatch): per-step logit
    MAE, the fp32 top-2 margin at every greedy disagreement, and (free-
    running only) the step index of the first token mismatch.
    """
    cfg, params = _model(arch, order)
    step_r, step_q = _steps(cfg)
    codec = QuantizedCodec(cfg=cfg, max_slots=1, n_max=N_MAX,
                           dtype=str(cfg.dtype), qdtype=qdtype)
    rng = np.random.default_rng(seed)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (1, PROMPT)), jnp.int32)
    cr = lm_init_caches(cfg, 1, N_MAX, jnp.dtype(cfg.dtype))
    cq = lm_init_caches(cfg, 1, N_MAX, jnp.dtype(cfg.dtype))
    tr = tq = None
    maes, flip_margins, first_mismatch = [], [], None
    for i in range(PROMPT + STEPS):
        if i < PROMPT:
            xr = xq = prompt[:, i]
        elif teacher_forced:
            xr = xq = tr
        else:
            xr, xq = tr, tq
        pos = jnp.asarray(i, jnp.int32)
        lr, cr = step_r(params, xr, cr, pos)
        lq, cq = step_q(params, xq, cq, pos, codec)
        tr = jnp.argmax(lr, -1).astype(jnp.int32)
        tq = jnp.argmax(lq, -1).astype(jnp.int32)
        if i >= PROMPT - 1:
            lrn, lqn = np.asarray(lr[0]), np.asarray(lq[0])
            maes.append(float(np.abs(lrn - lqn).mean()))
            if int(tr[0]) != int(tq[0]):
                top2 = np.partition(lrn, -2)
                flip_margins.append(float(top2[-1] - top2[-2]))
                if first_mismatch is None:
                    first_mismatch = i - (PROMPT - 1)
    return maes, flip_margins, first_mismatch


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("order", [1, 2])
@pytest.mark.parametrize("qdtype", ["int8", "fp8"])
def test_bounded_mae_and_margin_stable_identity(arch, order, qdtype):
    """Teacher-forced state error stays bounded over the full window and
    never flips a margin-stable greedy decision — per dtype, per order,
    GQA and MQA, two seeds."""
    for seed in (0, 1):
        maes, flip_margins, _ = _run_pair(arch, order, qdtype, seed,
                                          teacher_forced=True)
        assert len(maes) == STEPS + 1
        assert max(maes) <= MAE_TOL[qdtype], \
            f"{arch} o{order} s{seed}: MAE {max(maes):.3f}"
        bad = [m for m in flip_margins if m >= MARGIN[qdtype]]
        assert not bad, \
            f"{arch} o{order} s{seed}: flipped stable decisions {bad}"


@pytest.mark.parametrize("qdtype", ["int8", "fp8"])
def test_free_running_identity_horizon(qdtype):
    """Free-running greedy decode (quantised tokens feed back) matches
    fp32 token-for-token to the pinned horizon; past it the sequences
    may fork but the teacher-forced MAE bound above still caps state
    error."""
    arch, order, seed = HORIZON_CELL[qdtype]
    _, _, first_mismatch = _run_pair(arch, order, qdtype, seed,
                                     teacher_forced=False)
    assert first_mismatch is None or first_mismatch >= HORIZON[qdtype], \
        f"diverged at step {first_mismatch} < horizon {HORIZON[qdtype]}"


def test_int8_strictly_tighter_than_fp8():
    """The pinned ordering: per-head pow2-scaled int8 beats fp8-e4m3 on
    state fidelity at these activation scales (7 vs 3 mantissa bits)."""
    worst = {"int8": 0.0, "fp8": 0.0}
    for arch in sorted(ARCHS):
        for order in (1, 2):
            for qd in ("int8", "fp8"):
                maes, _, _ = _run_pair(arch, order, qd, 0, teacher_forced=True)
                worst[qd] = max(worst[qd], max(maes))
    assert worst["int8"] < worst["fp8"]
