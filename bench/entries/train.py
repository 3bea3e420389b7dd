"""Training steps back to back through the program's jitted train step
(``repro.launch.train.make_sharded_state_and_step``), fed by its
synthetic input pipeline.

Set-up builds the step and its state once, puts the benchmark's seeded
weights in it, and drives it through its first three steps with the
window's own call and feed; those steps compile the step (or read it from
the cache) and give what the check compares.  The window then runs steps
from step 4 on, with at most two in flight, and ends in
``block_until_ready``.

Check, after the window, against the plain reference following the same
three steps from the same weights on the same rows (AdamW of its own):

* ``loss_gap``: the largest ``|loss − loss_ref|`` over the three steps;
* ``grad_gap``: the first gradient as the optimizer got it (its first
  moment after one step over ``1 − β1``), by the worst per-layer leaf:
  ``|‖g‖ − ‖g_ref‖|`` against the larger of the leaf's ``‖g_ref‖`` and
  the median leaf's;
* ``change_gap``: the same for the change of the parameters over the
  three steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (round-off alone moves those).
"""

from __future__ import annotations

import collections
import gc
import time

import numpy as np

from bench import weights
from bench.entries.common import layer_norms, model_config, worst_leaf_gap
from bench.harness import Check, log, memory_peak, span

FIRST_STEPS = 3
IN_FLIGHT = 2
TRACE_AT, TRACE_STEPS = 0.35, 2
ZERO_GRAD = 1e-3


def optimizer(opt: dict):
    from repro.optim import adamw, cosine_warmup  # noqa: PLC0415

    if opt["name"] != "adamw":
        raise ValueError(f"unsupported optimizer {opt['name']!r}")
    sched = cosine_warmup(opt["lr"], opt["warmup"], opt["total_steps"], opt["final_frac"])
    return adamw(sched, b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                 weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"])


def run_cell(run, limits, clock, tracer, t_start, devices):
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    from repro.data import make_task  # noqa: PLC0415
    from repro.distributed import api as dist  # noqa: PLC0415
    from repro.launch.mesh import make_host_mesh  # noqa: PLC0415
    from repro.launch.train import make_sharded_state_and_step  # noqa: PLC0415

    sysd = run.config["system"]
    opt = sysd["optimizer"]
    cfg = model_config(run.config)
    batch, seq = sysd["batch"], sysd["seq"]
    mesh = make_host_mesh(1, 1)
    rules = dist.rules_for_mesh(mesh)
    task = make_task(sysd["task"], cfg.vocab, seq, batch, seed=run.seed)
    shapes = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32) for k in ("tokens", "labels")}
    state, step_fn, state_ns, batch_ns = make_sharded_state_and_step(
        cfg, optimizer(opt), mesh, rules, shapes, seed=run.seed & 0x7FFFFFFF)
    params = weights.make(weights.layout(cfg, jnp.float32), run.seed)
    state = state._replace(params=jax.device_put(params, state_ns.params))
    del params

    def feed(step: int):
        with span("bench.train.batch"):
            host = task.batch_at(step)
            return {k: jax.device_put(host[k], batch_ns[k]) for k in shapes}

    def call(state, b):
        with span("bench.train.dispatch"), dist.sharding_rules(mesh, rules):
            return step_fn(state, b)

    # -- set-up: the first steps, through the window's own call and feed --
    p0 = jax.tree.map(jnp.copy, state.params)
    losses, grad_norms = [], None
    for s in range(FIRST_STEPS):
        state, metrics = call(state, feed(s))
        losses.append(float(metrics["loss"]))
        if s == 0:
            g = jax.tree.map(lambda m: m / (1 - opt["b1"]), state.opt_state.m)
            grad_norms = layer_norms(g)
            del g
    change_norms = layer_norms(jax.tree.map(jnp.subtract, state.params, p0))
    del p0
    run.setup_s = time.monotonic() - t_start
    log(f"[setup] setup_s={run.setup_s:.3f} {clock.describe()}; losses {losses}")

    # -- window -----------------------------------------------------------
    compiled0 = clock.compiled
    step = FIRST_STEPS
    in_flight = collections.deque()
    traced = 0
    t0 = time.monotonic()
    t_end = t0 + run.seconds
    while time.monotonic() < t_end or tracer.active:
        if not tracer.done and time.monotonic() >= t0 + TRACE_AT * run.seconds:
            jax.block_until_ready(state)
            in_flight.clear()
            tracer.start()
        state, metrics = call(state, feed(step))
        step += 1
        in_flight.append(metrics["loss"])
        if tracer.active:
            traced += 1
            if traced == TRACE_STEPS:
                jax.block_until_ready(state)
                tracer.stop()
        if len(in_flight) > IN_FLIGHT:
            in_flight.popleft().block_until_ready()
    jax.block_until_ready(state)
    run.window_s = time.monotonic() - t0
    run.compiles_in_window = clock.compiled - compiled0
    run.memory_peak_bytes = memory_peak(devices)
    steps = step - FIRST_STEPS
    run.attempted, run.failed = steps, 0
    run.data.update(cfg=cfg, batch=batch, seq=seq, steps=steps, traced_steps=traced, task=task)
    log(f"[window] {steps} steps of {batch}x{seq} in {run.window_s:.3f} s; "
        f"compiles in window {run.compiles_in_window}")
    del state, metrics, in_flight
    gc.collect()
    with span("bench.check"):
        run.checks = check(run, task, losses, grad_norms, change_norms, limits)


def reference_steps(run, task, steps: int, rows=None, dtype=None):
    """The plain reference that the configuration names, through ``steps``
    AdamW steps from the seeded weights on the rows the program saw:
    ``(losses, grad_norms of step 1, change_norms after the last step)``.
    ``rows`` limits each batch to its first rows; ``dtype`` runs the
    reference in another precision (both for the control)."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    from bench.reference import adamw  # noqa: PLC0415

    ref = run.reference
    dtype = dtype or jnp.float32
    spec = ref.Spec.from_config(run.config)
    opt = run.config["system"]["optimizer"]
    cfg = run.data["cfg"]
    p0 = weights.make(weights.layout(cfg, jnp.float32), run.seed)
    params = jax.tree.map(lambda x: x.astype(dtype), p0)
    state = adamw.init(params)
    losses, grad_norms = [], None
    for t in range(1, steps + 1):
        b = task.batch_at(t - 1)
        n = rows or b["tokens"].shape[0]
        total, loss = None, 0.0
        for r in range(n):
            lr, g = ref.row_loss_and_grad(spec, params, b["tokens"][r], b["labels"][r], dtype)
            loss += float(lr)
            total = g if total is None else jax.tree.map(jnp.add, total, g)
        grads = adamw.clip(opt, jax.tree.map(lambda x: x.astype(jnp.float32) / n, total))
        losses.append(loss / n)
        if t == 1:
            grad_norms = layer_norms(grads)
        params, state = adamw.step(opt, t, params, state, grads, dtype)
    change = jax.tree.map(lambda p, q: p.astype(jnp.float32) - q, params, p0)
    return losses, grad_norms, layer_norms(change)


def compare(losses, grad_norms, change_norms, ref) -> dict:
    """The three numbers the check compares, program against reference."""
    r_losses, r_grads, r_change = ref
    med = float(np.median(list(r_grads.values())))
    moving = {k for k, v in r_grads.items() if v >= ZERO_GRAD * med}
    g_gap, g_leaf = worst_leaf_gap(grad_norms, r_grads)
    c_gap, c_leaf = worst_leaf_gap(change_norms, r_change, keep=moving)
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(losses, r_losses)),
        "grad_gap": g_gap, "grad_leaf": g_leaf,
        "change_gap": c_gap, "change_leaf": c_leaf,
        "ref_losses": r_losses, "left_out": sorted(set(r_grads) - moving),
    }


def check(run, task, losses, grad_norms, change_norms, limits) -> list:
    ref = run.data["reference"] = reference_steps(run, task, len(losses))
    got = compare(losses, grad_norms, change_norms, ref)
    log(f"[check] losses {losses} reference {got['ref_losses']}; worst grad leaf "
        f"{got['grad_leaf']}, worst change leaf {got['change_leaf']}; "
        f"left out of the change: {got['left_out']}")
    return [Check(k, float(got[k]), limits[k]) for k in ("loss_gap", "grad_gap", "change_gap")]
