"""A reference module for the tests: the default reference with every
logit's order reversed and a loss one higher, so that a check that runs it
in place of the default fails in a way that shows which module it ran."""

import jax.numpy as jnp

from bench.reference import model

FP8 = model.FP8
Spec = model.Spec


def logits_at(spec, params, tokens, idx, dtype=jnp.float32):
    return model.logits_at(spec, params, tokens, idx, dtype=dtype)[:, ::-1]


def row_loss_and_grad(spec, params, tokens, labels, dtype=jnp.float32):
    loss, grads = model.row_loss_and_grad(spec, params, tokens, labels, dtype=dtype)
    return loss + 1.0, grads
