"""Seeded weights: a rule for every leaf that the program stores for each
language-model preset, and the same draws as before for the cells'
configurations."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.entries.common import model_config
from bench.tests import tiny
from repro.configs import ARCHS, get_reduced

LM_ARCHS = [a for a in ARCHS if get_reduced(a).family == "lm"]

# sha256 over the leaves' bytes, in the layout's order, of the tiny cells'
# weights at seed 2**31 + 3, recorded before rules were added for the
# leaves these configurations do not have
DIGESTS = {
    ("tiny-serve", "float32"): "ca19bfc0d41556bc15af794d2d4a94ecac4968ed3601289ca05ff2cb5ebbb881",
    ("tiny-serve", "bfloat16"): "68b5abd86d1b30fff18ffe339adb5a08148df162829c41febe005fb6a89cf888",
    ("tiny-train", "float32"): "f7941af6cd5db252329b8f175d2bf3425cab07f85c6cde90b2ea50bf3772a675",
    ("tiny-train", "bfloat16"): "dcc411f220cf741b809b4e89d10a541e5365023f062c2f706789c4e72e0ad074",
}


def _leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {".".join(weights._names(path)): np.asarray(x, np.float32) for path, x in flat}


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_every_leaf_of_every_language_model_has_a_rule(arch):
    shapes = weights.layout(get_reduced(arch), jnp.bfloat16)
    params = weights.make(shapes, 2**33 + 7)
    for got, want in zip(jax.tree.leaves(params), jax.tree.leaves(shapes)):
        assert (got.shape, got.dtype) == (want.shape, want.dtype)
    for name, x in _leaves(params).items():
        assert np.isfinite(x).all(), name
        last = name.rsplit(".", 1)[-1]
        if last == "A_log":  # A = −exp(A_log) in [−16, −1]
            a = -np.exp(x)
            assert (a < 0).all() and a.min() >= -16.0 - 1e-4 and a.max() <= -1.0 + 1e-5, name
        elif last == "dt_bias":
            dt = np.asarray(jax.nn.softplus(x))
            assert dt.min() >= weights.DT_MIN * 0.999 and dt.max() <= weights.DT_MAX * 1.001, name
        elif last in ("scale", "D"):
            assert abs(x.mean() - 1.0) < 0.05, name
        else:
            assert x.std() > 0, name


def test_a_leaf_without_a_rule_is_refused():
    with pytest.raises(ValueError, match="no rule for weight blocks.mystery.w"):
        weights._leaf(("blocks", "mystery", "w"), (4, 4), jax.random.PRNGKey(0))


@pytest.mark.parametrize("name,dtype", sorted(DIGESTS))
def test_the_cells_weights_are_drawn_as_before(tmp_path, name, dtype):
    bench = tiny.write(tmp_path)
    cfg = model_config(json.loads((bench / "configs" / f"{name}.json").read_text()))
    params = weights.make(weights.layout(cfg, jnp.dtype(dtype)), 2**31 + 3)
    h = hashlib.sha256()
    for x in jax.tree.leaves(params):
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == DIGESTS[name, dtype]
