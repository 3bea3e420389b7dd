"""The plain reference against the system at a reduced size on the CPU:
logits of a whole sequence (serving's reference) and the loss and its
gradients (training's)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.entries.common import model_config
from bench.reference import adamw
from bench.reference import model as ref
from bench.tests import tiny


@pytest.fixture(scope="module", params=["qwen2-1.5b", "smollm-135m"])
def small(request, tmp_path_factory):
    bench = tiny.write(tmp_path_factory.mktemp(request.param))
    name = "tiny-serve" if request.param == "qwen2-1.5b" else "tiny-train"
    doc = json.loads((bench / "configs" / f"{name}.json").read_text())
    cfg = model_config(doc).replace(attn_impl="xla", remat="none")
    params = weights.make(weights.layout(cfg, jnp.float32), 2**31 + 3)
    return doc, cfg, params


def test_reference_logits_match_the_system(small):
    from repro.models import lm_apply

    doc, cfg, params = small
    tokens = np.random.default_rng(0).integers(0, cfg.vocab, 96).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = lm_apply(params, {"tokens": tokens[None]}, cfg)
    got = ref.logits_at(ref.Spec.from_config(doc), params, tokens, np.arange(96))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]), atol=2e-4, rtol=2e-4)


def test_reference_loss_and_gradients_match_the_system(small):
    from repro.train.step import make_loss_fn

    doc, cfg, params = small
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab, (2, 64)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab, (2, 64)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        (_, aux), want = jax.value_and_grad(make_loss_fn(cfg), has_aux=True)(
            params, {"tokens": tokens, "labels": labels})
    spec = ref.Spec.from_config(doc)
    rows = [ref.row_loss_and_grad(spec, params, tokens[r], labels[r]) for r in range(2)]
    loss = sum(float(l) for l, _ in rows) / 2
    grads = jax.tree.map(lambda a, b: (a + b) / 2, rows[0][1], rows[1][1])
    assert loss == pytest.approx(float(aux["loss"]), abs=1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5, rtol=1e-3)


def test_reference_adamw_matches_the_programs_optimizer(small):
    from repro.optim import apply_updates

    from bench.entries.train import optimizer

    doc, _, params = small
    opt = json.loads((tiny.BENCH / "configs" / "smollm-135m.json").read_text())["system"]["optimizer"]
    prog = optimizer(opt)
    p_state, p_params = prog.init(params), params
    r_state, r_params = adamw.init(params), params
    key = jax.random.PRNGKey(0)
    for t in range(1, 4):
        key, sub = jax.random.split(key)
        grads = jax.tree.map(lambda x: jax.random.normal(sub, x.shape) * 0.3, params)
        updates, p_state = prog.update(grads, p_state, p_params)
        p_params = apply_updates(p_params, updates)
        r_params, r_state = adamw.step(opt, t, r_params, r_state, adamw.clip(opt, grads))
    for a, b in zip(jax.tree.leaves(p_params), jax.tree.leaves(r_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7, rtol=1e-6)


def test_reference_attention_by_blocks_of_queries_matches_one_block(monkeypatch):
    """The O(n²) attention, its queries taken a few rows at a time, is the
    same as all at once."""
    spec = ref.Spec(layers=1, d_model=64, heads=4, kv_heads=2, d_ff=8, vocab=8,
                    rope_theta=1e4, norm_eps=1e-6)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (100, 4, 16))
    k, v = (jax.random.normal(x, (100, 2, 16)) for x in keys[1:])
    whole = ref.taylor_attention(spec, q, k, v)
    monkeypatch.setattr(ref, "ROWS", 32)
    blocks = ref.taylor_attention(spec, q, k, v)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole), rtol=1e-6, atol=1e-6)
