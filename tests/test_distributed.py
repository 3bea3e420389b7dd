"""Distributed correctness: sharding rules + multi-device subprocess tests.

Multi-device tests spawn a fresh python with
``--xla_force_host_platform_device_count=8`` so the main test process keeps
seeing exactly 1 device (the dry-run owns the 512-device trick)."""

import json
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_reduced
from repro.distributed.api import SINGLE_POD_RULES, rules_for_mesh
from repro.distributed.sharding import opt_state_specs, param_specs, spec_for
from repro.models import lm_init
from repro.optim import adamw, constant


def _run_subprocess(code: str) -> str:
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600, env=env, cwd="/root/repo",
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


class FakeMesh:
    """Just enough of a Mesh for spec_for's divisibility checks."""

    def __init__(self, sizes):
        self.shape = dict(sizes)
        self.axis_names = tuple(sizes)


def test_spec_rules_divisibility_fallback():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = dict(SINGLE_POD_RULES)
    # wk with 2 kv heads: 2 % 16 != 0 -> tp dropped on that dim
    assert spec_for("blocks.group.b0.attn.wk.w", (28, 1536, 2, 128), rules, mesh) == P(
        None, "data", None, None
    )
    # wq with 48 heads: sharded over model
    assert spec_for("blocks.group.b0.attn.wq.w", (52, 6144, 48, 128), rules, mesh) == P(
        None, "data", "model", None
    )
    # experts over ep(model) + fsdp(data)
    assert spec_for(
        "blocks.group.b0.moe.experts.w_gate", (61, 384, 7168, 2048), rules, mesh
    ) == P(None, "model", "data", None)
    # norm scale replicated
    assert spec_for("final_norm.scale", (1536,), rules, mesh) == P()
    # embed: vocab over tp, d over fsdp
    assert spec_for("embed.w", (151936, 1536), rules, mesh) == P("model", "data")


def test_param_and_opt_specs_cover_every_leaf():
    cfg = get_config("qwen2-moe-a2.7b")
    key = jax.ShapeDtypeStruct((2,), "uint32")
    pshapes = jax.eval_shape(lambda k: lm_init(k, cfg), key)
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = dict(SINGLE_POD_RULES)
    pspecs = param_specs(pshapes, mesh, rules)
    assert jax.tree_util.tree_structure(pshapes) == jax.tree_util.tree_structure(
        pspecs, is_leaf=lambda x: isinstance(x, P)
    )
    opt = adamw(constant(1e-3))
    oshapes = jax.eval_shape(opt.init, pshapes)
    ospecs = opt_state_specs(oshapes, pspecs, pshapes, mesh, rules)
    # m/v inherit the param spec; step is replicated
    flat_p = jax.tree_util.tree_leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
    flat_m = jax.tree_util.tree_leaves(ospecs.m, is_leaf=lambda x: isinstance(x, P))
    assert flat_p == flat_m


def test_sharded_training_matches_single_device():
    """Same seed/data: 2x4 sharded training == unsharded training."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.configs import get_reduced
        from repro.data import make_task
        from repro.optim import adamw, constant
        from repro.launch.train import make_sharded_state_and_step
        from repro.train.step import make_train_step, train_state_init
        from repro.distributed import api as dist
        from repro.launch.mesh import make_host_mesh

        cfg = get_reduced("qwen2-1.5b")
        task = make_task("bigram", cfg.vocab, 32, 8, seed=3)
        batch_shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                        for k, v in task.batch_at(0).items()}

        # single-device reference
        opt = adamw(constant(1e-3))
        state = train_state_init(jax.random.PRNGKey(0), cfg, opt)
        step = jax.jit(make_train_step(cfg, opt))
        losses_ref = []
        for s in range(3):
            batch = {k: jnp.asarray(v) for k, v in task.batch_at(s).items()}
            state, m = step(state, batch)
            losses_ref.append(float(m["loss"]))

        # sharded 2x4
        mesh = make_host_mesh(2, 4)
        rules = dist.rules_for_mesh(mesh)
        opt2 = adamw(constant(1e-3))
        state2, step_fn, _, _ = make_sharded_state_and_step(
            cfg, opt2, mesh, rules, batch_shapes, seed=0)
        losses_sh = []
        for s in range(3):
            batch = {k: jnp.asarray(v) for k, v in task.batch_at(s).items()}
            with dist.sharding_rules(mesh, rules):
                state2, m = step_fn(state2, batch)
            losses_sh.append(float(m["loss"]))
        print(json.dumps({"ref": losses_ref, "sh": losses_sh}))
    """)
    data = json.loads(out.strip().splitlines()[-1])
    for a, b in zip(data["ref"], data["sh"]):
        assert abs(a - b) < 2e-3, data


def test_elastic_reshard_restore():
    """Checkpoint written on a 2x4 mesh restores onto 4x2 and 1x1."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np, json, tempfile
        from repro.configs import get_reduced
        from repro.optim import adamw, constant
        from repro.train.step import train_state_init
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        from repro.distributed import api as dist
        from repro.distributed.sharding import param_specs, opt_state_specs, named_shardings
        from repro.launch.mesh import make_host_mesh
        from repro.train.step import TrainState

        cfg = get_reduced("smollm-135m")
        opt = adamw(constant(1e-3))
        state = train_state_init(jax.random.PRNGKey(0), cfg, opt)
        d = tempfile.mkdtemp()
        save_checkpoint(d, 5, state)

        for shape in ((2, 4), (4, 2), (1, 1)):
            mesh = make_host_mesh(*shape)
            rules = dist.rules_for_mesh(mesh)
            pshapes = jax.eval_shape(lambda: state.params)
            pspecs = param_specs(pshapes, mesh, rules)
            oshapes = jax.eval_shape(lambda: state.opt_state)
            ospecs = opt_state_specs(oshapes, pspecs, pshapes, mesh, rules)
            from jax.sharding import PartitionSpec as P
            sspecs = TrainState(step=P(), params=pspecs, opt_state=ospecs)
            ns = named_shardings(sspecs, mesh)
            back = restore_checkpoint(d, state, shardings=ns)
            leaves_a = jax.tree_util.tree_leaves(state.params)
            leaves_b = jax.tree_util.tree_leaves(back.params)
            for a, b in zip(leaves_a, leaves_b):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)
        print("RESHARD_OK")
    """)
    assert "RESHARD_OK" in out


def test_cp_attention_training_matches_tp():
    """§Perf cell C: model trained with context-parallel attention must
    produce identical losses to the TP-sharded baseline."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, json
        from repro.configs import get_reduced
        from repro.data import make_task
        from repro.optim import adamw, constant
        from repro.launch.train import make_sharded_state_and_step
        from repro.distributed import api as dist
        from repro.launch.mesh import make_host_mesh

        losses = {}
        for mode in ("tp", "cp"):
            cfg = get_reduced("granite-20b").replace(
                attn_sharding=mode, attn_chunk=8, max_seq=256)
            task = make_task("bigram", cfg.vocab, 64, 8, seed=3)
            shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                      for k, v in task.batch_at(0).items()}
            mesh = make_host_mesh(2, 4)
            rules = dist.rules_for_mesh(mesh)
            state, step_fn, _, _ = make_sharded_state_and_step(
                cfg, adamw(constant(1e-3)), mesh, rules, shapes, seed=0)
            ls = []
            for s in range(2):
                batch = {k: jnp.asarray(v) for k, v in task.batch_at(s).items()}
                with dist.sharding_rules(mesh, rules):
                    state, m = step_fn(state, batch)
                ls.append(float(m["loss"]))
            losses[mode] = ls
        print(json.dumps(losses))
    """)
    data = json.loads(out.strip().splitlines()[-1])
    for a, b in zip(data["tp"], data["cp"]):
        assert abs(a - b) < 5e-3, data


def test_context_parallel_state_exchange():
    """SP/CP for the paper's attention: shard the sequence over devices,
    exchange only the O(d²·d_v) moment state — outputs must match the
    unsharded chunked run (DESIGN.md §2.3)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import TaylorConfig, taylor_attention_chunked
        from repro.core.context_parallel import taylor_attention_context_parallel
        from jax.sharding import AxisType, PartitionSpec as P

        mesh = jax.make_mesh((8,), ("seq",), axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(0)
        b, h, hk, n, d, dv = 1, 2, 1, 512, 16, 16
        q = jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, hk, n, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, hk, n, dv)), jnp.float32)
        cfg = TaylorConfig()
        ref = taylor_attention_chunked(q, k, v, cfg, chunk=64)
        out = taylor_attention_context_parallel(q, k, v, cfg, mesh, "seq", chunk=64)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=5e-5)
        print("CP_OK")
    """)
    assert "CP_OK" in out


def test_ssd_context_parallel_exact():
    """SSD (Mamba2) context parallelism: decay-weighted state exchange must
    match the unsharded chunked scan, fwd and grad."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.ssm import _ssd_chunked
        from repro.core.ssd_context_parallel import ssd_context_parallel
        from jax.sharding import AxisType

        mesh = jax.make_mesh((8,), ("seq",), axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(0)
        b, n, H, Pd, G, N = 2, 512, 4, 16, 1, 8
        x = jnp.asarray(rng.normal(size=(b, n, H, Pd)), jnp.float32)
        dt = jnp.asarray(np.abs(rng.normal(size=(b, n, H))) * 0.1, jnp.float32)
        A = -jnp.asarray(np.abs(rng.normal(size=(H,))) + 0.5, jnp.float32)
        B = jnp.asarray(rng.normal(size=(b, n, G, N)), jnp.float32)
        C = jnp.asarray(rng.normal(size=(b, n, G, N)), jnp.float32)
        ref = _ssd_chunked(x, dt, A, B, C, chunk=64)
        out = ssd_context_parallel(x, dt, A, B, C, mesh, "seq", chunk=64)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-4)
        t = jnp.asarray(rng.normal(size=ref.shape), jnp.float32)
        g1 = jax.grad(lambda x: jnp.sum(_ssd_chunked(x, dt, A, B, C, chunk=64) * t))(x)
        g2 = jax.grad(lambda x: jnp.sum(
            ssd_context_parallel(x, dt, A, B, C, mesh, "seq", chunk=64) * t))(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)
        print("SSD_CP_OK")
    """)
    assert "SSD_CP_OK" in out


def test_pallas_training_sharded_matches_single_device():
    """The Pallas kernel pair runs once per shard (``shard_map``) on a
    mesh: data- and tensor-parallel training losses match one device."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, json
        from repro.configs import get_reduced
        from repro.data import make_task
        from repro.optim import adamw, constant
        from repro.launch.train import make_sharded_state_and_step
        from repro.distributed import api as dist
        from repro.launch.mesh import make_host_mesh

        cfg = get_reduced("smollm-135m").replace(attn_impl="pallas")
        task = make_task("bigram", cfg.vocab, 32, 8, seed=3)
        shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for k, v in task.batch_at(0).items()}
        losses = {}
        for shape in ((1, 1), (4, 2), (8, 1)):
            mesh = make_host_mesh(*shape)
            rules = dist.rules_for_mesh(mesh)
            state, step_fn, _, _ = make_sharded_state_and_step(
                cfg, adamw(constant(1e-3)), mesh, rules, shapes, seed=0)
            ls = []
            for s in range(2):
                batch = {k: jnp.asarray(v) for k, v in task.batch_at(s).items()}
                with dist.sharding_rules(mesh, rules):
                    state, m = step_fn(state, batch)
                ls.append(float(m["loss"]))
            losses[str(shape)] = ls
        print(json.dumps(losses))
    """)
    data = json.loads(out.strip().splitlines()[-1])
    ref = data.pop("(1, 1)")
    for shape, ls in data.items():
        for a, b in zip(ref, ls):
            assert abs(a - b) < 2e-3, (shape, data, ref)
