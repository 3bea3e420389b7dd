"""Tests for the §Perf beyond-paper features: symmetric-compressed states,
int8 expert all_to_all, query-chunked non-causal attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_qkv
from repro.core import (
    TaylorConfig,
    init_taylor_state,
    taylor_attention_chunked,
    taylor_attention_noncausal,
    taylor_attention_parallel,
    taylor_attention_recurrent,
)

FULL = TaylorConfig(order=2)
SYM = TaylorConfig(order=2, sym_state=True)


def test_sym_state_exact_and_smaller(rng):
    q, k, v = make_qkv(rng)
    ref = taylor_attention_parallel(q, k, v, FULL)
    np.testing.assert_allclose(
        np.asarray(taylor_attention_chunked(q, k, v, SYM, chunk=16)),
        np.asarray(ref), atol=5e-5,
    )
    np.testing.assert_allclose(
        np.asarray(taylor_attention_recurrent(q, k, v, SYM)),
        np.asarray(ref), atol=5e-5,
    )
    nbytes = lambda c: sum(
        x.size for x in jax.tree_util.tree_leaves(init_taylor_state(1, 1, 16, 16, c))
    )
    assert nbytes(SYM) < 0.62 * nbytes(FULL)  # d(d+1)/2 vs d² second moments


def test_noncausal_query_chunking_exact(rng):
    """The chunked-query scan (memory fix #9) must not change results."""
    q, k, v = make_qkv(rng, n=64)
    a = taylor_attention_noncausal(q, k, v, FULL, chunk=16)  # chunked path
    b = taylor_attention_noncausal(q, k, v, FULL, chunk=4096)  # single pass
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_noncausal_chunking_grads(rng):
    q, k, v = make_qkv(rng, n=64)
    t = jnp.asarray(np.random.default_rng(1).normal(size=(2, 4, 64, 16)), jnp.float32)

    def loss(chunk):
        return lambda q, k, v: jnp.sum(
            taylor_attention_noncausal(q, k, v, FULL, chunk=chunk) * t
        )

    g1 = jax.grad(loss(16), (0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(4096), (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_int8_a2a_moe_close_to_exact():
    """int8 dispatch quantization: outputs near the exact path, grads flow."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.models.config import MoEConfig
        from repro.models import moe as moe_mod
        from repro.distributed import api as dist

        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(2, 4)
        rules = dist.rules_for_mesh(mesh)
        base = MoEConfig(n_experts=8, top_k=2, d_ff_expert=32,
                         capacity_factor=8.0, impl="ep_a2a")
        cfg = get_reduced("qwen2-moe-a2.7b").replace(moe=base)
        params = moe_mod.moe_init(jax.random.PRNGKey(2), cfg)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16, cfg.d_model)),
                        jnp.float32)
        import dataclasses
        cfg8 = cfg.replace(moe=dataclasses.replace(base, a2a_quant="int8"))
        with dist.sharding_rules(mesh, rules):
            y, _ = jax.jit(lambda p, x: moe_mod.moe_apply(p, x, cfg))(params, x)
            y8, _ = jax.jit(lambda p, x: moe_mod.moe_apply(p, x, cfg8))(params, x)
            g = jax.jit(jax.grad(lambda p: jnp.sum(
                moe_mod.moe_apply(p, x, cfg8)[0] ** 2)))(params)
        rel = float(jnp.max(jnp.abs(y - y8)) / (jnp.max(jnp.abs(y)) + 1e-9))
        gn = sum(float(jnp.sum(jnp.abs(v))) for v in jax.tree_util.tree_leaves(g))
        assert rel < 0.05, rel      # int8 quantization error bound
        assert gn > 0 and np.isfinite(gn)
        print("INT8_OK", rel)
    """)
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-3000:]
    assert "INT8_OK" in out.stdout
