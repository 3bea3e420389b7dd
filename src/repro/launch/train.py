"""Training launcher.

Runs real training (CPU-scale with --reduced; production mesh on TPU) with
the full substrate: sharded state, fault-tolerant loop, deterministic data.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --steps 200 --batch 8 --seq 256 --data bigram --ckpt-dir /tmp/ckpt

Re-invoking the same command after an interruption resumes from the newest
committed checkpoint (exactly — the data pipeline is stateless in step).
"""

from __future__ import annotations

import argparse
import time
from typing import Callable, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs import ARCHS, get_config, get_reduced
from repro.data import make_task
from repro.distributed import api as dist
from repro.distributed.sharding import (
    batch_specs,
    named_shardings,
    opt_state_specs,
    param_specs,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import lm_init
from repro.models.config import ModelConfig, count_params
from repro.optim import adafactor, adamw, cosine_warmup, sgdm
from repro.train import TrainLoopConfig, TrainState, make_train_step, run_training


def build_optimizer(name: str, lr: float, warmup: int, total: int):
    sched = cosine_warmup(lr, warmup, total)
    if name == "adamw":
        return adamw(sched)
    if name == "adafactor":
        return adafactor(sched)
    if name == "sgdm":
        return sgdm(sched)
    raise ValueError(name)


def make_sharded_state_and_step(cfg, optimizer, mesh, rules, batch_shapes, seed=0):
    """Init state ON the mesh (sharded from birth via jit out_shardings)."""
    key = jax.ShapeDtypeStruct((2,), "uint32")
    pshapes = jax.eval_shape(lambda k: lm_init(k, cfg), key)
    oshapes = jax.eval_shape(optimizer.init, pshapes)
    pspecs = param_specs(pshapes, mesh, rules)
    ospecs = opt_state_specs(oshapes, pspecs, pshapes, mesh, rules)
    from jax.sharding import NamedSharding, PartitionSpec as P

    state_specs = TrainState(step=P(), params=pspecs, opt_state=ospecs)
    state_ns = named_shardings(state_specs, mesh)
    bspecs = batch_specs(batch_shapes, mesh, rules)
    batch_ns = named_shardings(bspecs, mesh)

    def init_fn(k):
        params = lm_init(k, cfg)
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=optimizer.init(params),
        )

    with dist.sharding_rules(mesh, rules):
        state = jax.jit(init_fn, out_shardings=state_ns)(
            jax.random.PRNGKey(seed)
        )
        step = make_train_step(cfg, optimizer)
        metrics_ns = {k: NamedSharding(mesh, P()) for k in
                      ("loss", "aux_loss", "total_loss")}
        step_fn = jax.jit(
            step,
            in_shardings=(state_ns, batch_ns),
            out_shardings=(state_ns, metrics_ns),
            donate_argnums=(0,),
        )
    return state, step_fn, state_ns, batch_ns


class TrainRun(NamedTuple):
    """What ``main`` leaves behind: the final state, the loss of every step
    this run took, and what it ran them with (the jitted sharded step, the
    mesh and rules it is traced under, and the data)."""

    state: TrainState
    losses: List[float]
    step_fn: Callable
    mesh: Mesh
    rules: dist.Rules
    batch_at: Callable[[int], Dict[str, jax.Array]]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--backend", choices=("softmax", "taylor", "linear_elu"))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "adafactor", "sgdm"))
    ap.add_argument("--data", default="bigram", choices=("bigram", "copy", "uniform"))
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--max-wall-seconds", type=float, default=None)
    return ap.parse_args(argv)


def config_from_args(args: argparse.Namespace) -> ModelConfig:
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.backend and not cfg.is_attention_free:
        cfg = cfg.replace(attention=args.backend)
    if args.seq % cfg.attn_chunk != 0:
        cfg = cfg.replace(attn_chunk=min(args.seq, cfg.attn_chunk))
    return cfg


def main(argv=None) -> TrainRun:
    args = parse_args(argv)
    print(f"[train] compile cache: {enable_compile_cache()}")
    cfg = config_from_args(args)

    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        mesh = make_host_mesh(args.mesh_data, args.mesh_model)
    rules = dist.rules_for_mesh(mesh)
    print(f"[train] {cfg.name} ({count_params(cfg):,} params) on mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))} backend={cfg.attention}")

    task = make_task(args.data, cfg.vocab, args.seq, args.batch, seed=args.seed)
    optimizer = build_optimizer(args.optimizer, args.lr, args.warmup, args.steps)

    batch_shapes = {
        "tokens": jax.ShapeDtypeStruct((args.batch, args.seq), "int32"),
        "labels": jax.ShapeDtypeStruct((args.batch, args.seq), "int32"),
    }
    extras = task.extras_at(0, cfg)
    for k, v in extras.items():
        batch_shapes[k] = jax.ShapeDtypeStruct(v.shape, v.dtype)

    state, step_fn, state_ns, _ = make_sharded_state_and_step(
        cfg, optimizer, mesh, rules, batch_shapes, seed=args.seed
    )

    def batch_at(step: int):
        b = dict(task.batch_at(step))
        b.update(task.extras_at(step, cfg))
        return {k: jnp.asarray(v) for k, v in b.items()}

    losses = []  # device scalars: reading them here would sync every step

    def wrapped_step(state, batch):
        with dist.sharding_rules(mesh, rules):
            state, metrics = step_fn(state, batch)
        losses.append(metrics["loss"])
        return state, metrics

    loop = TrainLoopConfig(
        total_steps=args.steps,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every,
        log_every=args.log_every,
        max_wall_seconds=args.max_wall_seconds,
    )
    t0 = time.monotonic()
    state = run_training(wrapped_step, state, batch_at, loop, state_shardings=state_ns)
    dt = time.monotonic() - t0
    final = int(jax.device_get(state.step))
    print(f"[train] done: step={final} wall={dt:.1f}s")
    return TrainRun(
        state=state, losses=[float(x) for x in losses], step_fn=step_fn,
        mesh=mesh, rules=rules, batch_at=batch_at,
    )


if __name__ == "__main__":
    main()
