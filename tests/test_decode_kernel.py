"""The Pallas decode kernel (interpret mode) against the XLA recurrence.

``taylor_decode_s2`` advances one layer of a stacked second moment by one
token and reads it out in the same pass.  Checked at qwen2-1.5b's head
widths (GQA 12/2, d = dv = 128, six slots) and at a tiny width: the step's
output and the updated rows agree with ``taylor_decode_step`` to f32
tolerance, ``num2`` with a float64 reference, rows whose ``keep`` is False
come back bit for bit, and only the named layer of the stack changes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TaylorConfig, taylor_decode_step, taylor_prefill_state
from repro.core.feature_map import layernorm_no_affine
from repro.kernels.taylor_attention.decode import taylor_decode_s2

# slots, q heads, kv heads, head dim (= value dim)
WIDTHS = {"qwen2-1.5b": (6, 12, 2, 128), "tiny": (3, 4, 2, 16)}
CFG = TaylorConfig()


def _step_inputs(rng, b, h, hk, d, prompt=8):
    """A state that has absorbed ``prompt`` random tokens, and one step's
    raw q/k/v."""
    normal = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    state = taylor_prefill_state(normal(b, hk, prompt, d), normal(b, hk, prompt, d), CFG)
    return state, normal(b, h, d), normal(b, hk, d), normal(b, hk, d)


def _keep(b):
    return np.arange(b) % 3 != 1  # rows 1, 4, ... do not advance


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_decode_kernel_matches_the_xla_recurrence(arch, rng):
    b, h, hk, d = WIDTHS[arch]
    state, q, k, v = _step_inputs(rng, b, h, hk, d)
    keep = _keep(b)
    stack = state.s2[None, None]  # one layer: [1, 1, b, hk, d, d, dv]
    old = np.asarray(state.s2)

    ref_out, ref_state = taylor_decode_step(state, q, k, v, CFG)

    def s2_pass(qg, kt, vt):
        return taylor_decode_s2(stack, jnp.zeros(2, jnp.int32), jnp.asarray(keep),
                                qg, kt, vt, interpret=True)

    out, new = taylor_decode_step(state, q, k, v, CFG, s2_pass=s2_pass)
    assert _rel(out, ref_out) < 2e-5
    got = np.asarray(new.s2)[0, 0]
    assert _rel(got[keep], np.asarray(ref_state.s2)[keep]) < 2e-6
    np.testing.assert_array_equal(got[~keep], old[~keep])
    for leaf in ("n0", "s0", "z1", "s1", "z2"):
        np.testing.assert_array_equal(np.asarray(getattr(new, leaf)),
                                      np.asarray(getattr(ref_state, leaf)))


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_decode_kernel_readout_matches_float64(arch, rng):
    """``num2`` is (q ⊗ q)·S2 of the ADVANCED second moment, for every row
    (kept or not), in f32 to f64 rounding."""
    b, h, hk, d = WIDTHS[arch]
    state, q, k, v = _step_inputs(rng, b, h, hk, d)
    qg = layernorm_no_affine(q).reshape(b, hk, h // hk, d)
    kn = layernorm_no_affine(k)
    num2, _ = taylor_decode_s2(state.s2[None, None], jnp.zeros(2, jnp.int32),
                               jnp.asarray(_keep(b)), qg, kn, v, interpret=True)
    q64, k64, v64 = (np.asarray(x, np.float64) for x in (qg, kn, v))
    s2 = np.asarray(state.s2, np.float64) + np.einsum(
        "bki,bkj,bkv->bkijv", k64, k64, v64)
    want = np.einsum("bkgi,bkgj,bkijv->bkgv", q64, q64, s2, optimize=True)
    assert _rel(num2, want) < 2e-6


def test_decode_kernel_changes_only_its_layer(rng):
    """Layer (1, 2) of a [2, 3, ...] stack advances (its kept rows); every
    other layer, and the rows not kept, come back bit for bit."""
    b, h, hk, d = WIDTHS["tiny"]
    stack = jnp.asarray(rng.normal(size=(2, 3, b, hk, d, d, d)), jnp.float32)
    before = np.asarray(stack)
    _, q, k, v = _step_inputs(rng, b, h, hk, d)
    keep = _keep(b)
    qg = q.reshape(b, hk, h // hk, d)
    _, after = taylor_decode_s2(stack, jnp.asarray([1, 2], jnp.int32),
                                jnp.asarray(keep), qg, k, v, interpret=True)
    after = np.asarray(after)
    for gi in range(2):
        for ri in range(3):
            if (gi, ri) != (1, 2):
                np.testing.assert_array_equal(after[gi, ri], before[gi, ri])
    np.testing.assert_array_equal(after[1, 2][~keep], before[1, 2][~keep])
    want = before[1, 2] + np.einsum("bki,bkj,bkv->bkijv", k, k, v)
    assert _rel(after[1, 2][keep], want[keep]) < 2e-6
