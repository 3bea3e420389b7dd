"""Bring-up smoke test: the serve engine and the training launcher on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py               # one chip: device, serve, train
    python chip_smoke.py --four-chips  # four chips: sharded serve and
                                       # data-parallel training only

One chip: serves qwen2-1.5b (28 layers, published widths, taylor backend,
seeded random weights) through ``ServeEngine`` and checks every greedy
token against a teacher-forced ``lm_apply`` (the Pallas forward kernel on
TPU); trains smollm-135m (30 layers, published widths) for 5 steps through
``repro.launch.train.main`` and checks the Pallas kernels are in the step
and that its losses agree with the XLA attention path.

Four chips: serves qwen2-1.5b on a 2x2 (slots x model) serve mesh against
the same requests on one device, and trains smollm-135m on a 4x1 data mesh
against one chip with the same global batch.

Exits non-zero, and prints no result line, when JAX finds no TPU or any
check fails.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SEED = 0
SERVE_ARCH = "qwen2-1.5b"
TRAIN_ARCH = "smollm-135m"
# (prompt length, submitted late?): lengths above PREFILL_CHUNK go through
# chunked prefill; the late request is submitted after decoding started.
REQUESTS = [(64, False), (1024, False), (256, False), (1500, False),
            (64, False), (700, False), (256, False), (128, True)]
NEW_TOKENS = 32
MAX_SLOTS, DECODE_BLOCK, PREFILL_CHUNK = 4, 16, 512
N_MAX = 2048          # also the padded length of the teacher-forced check
TRAIN_ARGS = ["--arch", TRAIN_ARCH, "--backend", "taylor", "--batch", "8",
              "--seq", "1024", "--log-every", "1", "--seed", str(SEED)]
TRAIN_STEPS = 5
# A greedy token must equal the teacher-forced argmax wherever the top-2
# logit margin exceeds MARGIN_TOL.  Random-init logits have unit variance
# over a 151936 vocab (top-2 gaps ~0.2), and the engine's bf16 decode
# recurrence and the bf16 kernel forward differ by a few hundredths.
MARGIN_TOL = 0.25
MIN_CHECKED = 0.1     # fraction of positions that must clear the margin
# |loss(Pallas) - loss(XLA)| per step, at ln(49152) = 10.8 nats.
LOSS_TOL = 0.02
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spent compiling (or reading the persistent cache), and
    persistent-cache hits, since construction."""

    def __init__(self):
        import jax  # noqa: PLC0415

        self.seconds, self.programs, self.hits = 0.0, 0, 0

        def on_duration(event, secs, **_):
            if event == COMPILE_EVENT:
                self.seconds += secs
                self.programs += 1

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.seconds, self.programs, self.hits

    def since(self, mark) -> str:
        s, p, h = mark
        return (f"compile {self.seconds - s:.1f} s over "
                f"{self.programs - p} programs, {self.hits - h} persistent-"
                f"cache hits")


# -- serving ----------------------------------------------------------------


def serve_setup(cfg):
    """Seeded bf16 serving weights and the request prompts."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    from repro.models import lm_init  # noqa: PLC0415

    params = jax.jit(lambda k: lm_init(k, cfg, dtype=jnp.bfloat16))(
        jax.random.PRNGKey(SEED)
    )
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n, _ in REQUESTS]
    return params, prompts


def serve(params, cfg, prompts, mesh=None):
    """Runs every request through ``ServeEngine``; returns the token arrays
    (in request order) and the engine's counters."""
    from repro.serve import Request, ServeEngine, Status  # noqa: PLC0415

    eng = ServeEngine(
        params, cfg, max_slots=MAX_SLOTS, n_max=N_MAX,
        decode_block=DECODE_BLOCK, prefill_chunk=PREFILL_CHUNK, mesh=mesh,
    )
    rids = [None] * len(prompts)
    for i, (p, (_, late)) in enumerate(zip(prompts, REQUESTS)):
        if not late:
            rids[i] = eng.submit(Request(tokens=p, max_new_tokens=NEW_TOKENS))
    for _ in range(3):  # decoding is under way when the late ones arrive
        eng.step()
    for i, (p, (_, late)) in enumerate(zip(prompts, REQUESTS)):
        if late:
            rids[i] = eng.submit(Request(tokens=p, max_new_tokens=NEW_TOKENS))
    results = eng.run(return_results=True)
    stats = eng.stats()
    for i, rid in enumerate(rids):
        r = results[rid]
        check(r.status is Status.OK,
              f"request {i} ended {r.status.name}: {r.error}")
        check(len(r.tokens) == NEW_TOKENS,
              f"request {i} produced {len(r.tokens)} tokens")
    for key in ("dispatch_failures", "cache_rebuilds", "quarantined"):
        n = stats.get(key, 0)
        check(n == 0, f"engine counted {key}={n}")
    return [np.asarray(results[r].tokens) for r in rids], stats


def teacher_forced(params, cfg, prompts, outputs):
    """Logits of ``lm_apply`` over prompt+output (padded to N_MAX, which a
    causal model cannot see), at the positions that predicted each output
    token: ``[requests, NEW_TOKENS, vocab]`` f32."""
    import functools  # noqa: PLC0415

    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    from repro.models import lm_apply  # noqa: PLC0415

    @functools.partial(jax.jit, static_argnames="cfg")
    def logits_at(params, tokens, idx, cfg):
        logits, _ = lm_apply(params, {"tokens": tokens}, cfg)
        return logits[0, idx].astype(jnp.float32)

    out = []
    for p, o in zip(prompts, outputs):
        seq = np.zeros((1, N_MAX), np.int32)
        seq[0, : len(p) + len(o)] = np.concatenate([p, o])
        idx = np.arange(len(p) - 1, len(p) - 1 + len(o), dtype=np.int32)
        out.append(np.asarray(logits_at(params, seq, idx, cfg)))
    return np.stack(out)


def margin_check(logits, outputs, what: str):
    """Every token whose reference top-2 margin exceeds MARGIN_TOL must be
    the reference argmax, and at least MIN_CHECKED of them must."""
    top2 = np.sort(logits, axis=-1)[..., -2:]
    margin = top2[..., 1] - top2[..., 0]
    toks = np.stack(outputs)
    sure = margin > MARGIN_TOL
    wrong = sure & (logits.argmax(-1) != toks)
    check(not wrong.any(),
          f"{what}: {int(wrong.sum())} tokens differ from the reference "
          f"argmax at margins {margin[wrong].round(3).tolist()[:8]}")
    check(sure.mean() >= MIN_CHECKED,
          f"{what}: only {int(sure.sum())}/{sure.size} positions have a "
          f"margin above {MARGIN_TOL}; the check would prove little")
    agree = float((logits.argmax(-1) == toks).mean())
    print(f"[serve] {what}: {int(sure.sum())}/{sure.size} tokens with "
          f"margin > {MARGIN_TOL} all match; overall argmax agreement "
          f"{agree:.4f}", flush=True)


def serve_phase(clock):
    import jax  # noqa: PLC0415

    from repro.configs import get_config  # noqa: PLC0415

    cfg = get_config(SERVE_ARCH)
    check(cfg.attention == "taylor", f"{cfg.name} is not on taylor")
    mark, t0 = clock.mark(), time.monotonic()
    params, prompts = serve_setup(cfg)
    outputs, stats = serve(params, cfg, prompts)
    print(f"[serve] {cfg.name}: {len(outputs)} requests OK "
          f"(prompts {[len(p) for p in prompts]}, {NEW_TOKENS} new tokens "
          f"each); dispatch_failures=0 cache_rebuilds=0 quarantined=0; "
          f"dispatches={stats['dispatches']} "
          f"prefill_dispatches={stats['prefill_dispatches']}", flush=True)
    kernel = teacher_forced(params, cfg, prompts, outputs)
    xla = teacher_forced(params, cfg.replace(attn_impl="xla"), prompts,
                         outputs)
    print(f"[serve] teacher-forced lm_apply: max |dlogit| Pallas kernel vs "
          f"XLA scan = {float(np.abs(kernel - xla).max()):.4f}", flush=True)
    margin_check(kernel, outputs, "engine vs kernel lm_apply")
    print(f"[serve] setup: {clock.since(mark)}; phase wall "
          f"{time.monotonic() - t0:.1f} s", flush=True)
    del params
    gc.collect()


# -- training ---------------------------------------------------------------


def train_losses(argv, steps):
    from repro.launch import train  # noqa: PLC0415

    run = train.main(argv + ["--steps", str(steps)])
    check(len(run.losses) == steps, f"{len(run.losses)} losses for {steps} steps")
    check(all(math.isfinite(x) for x in run.losses),
          f"non-finite loss: {run.losses}")
    return run


def train_phase(clock):
    import jax  # noqa: PLC0415

    from repro.distributed import api as dist  # noqa: PLC0415
    from repro.launch import train  # noqa: PLC0415

    mark, t0 = clock.mark(), time.monotonic()
    run = train_losses(TRAIN_ARGS, TRAIN_STEPS)
    with dist.sharding_rules(run.mesh, run.rules):
        text = run.step_fn.lower(run.state, run.batch_at(0)).as_text()
    kernels = text.count("tpu_custom_call")
    check(kernels > 0, "the train step holds no Pallas kernel")
    print(f"[train] losses {run.losses}; tpu_custom_call in the step "
          f"({kernels} sites)", flush=True)

    # The same first two steps with XLA attention, from the same init and
    # batches: step 2's loss reads the Pallas backward's gradients.
    args = train.parse_args(TRAIN_ARGS + ["--steps", str(TRAIN_STEPS)])
    cfg = train.config_from_args(args).replace(attn_impl="xla")
    batches = [run.batch_at(0), run.batch_at(1)]
    shapes = jax.eval_shape(lambda: batches[0])
    state, step_fn, _, _ = train.make_sharded_state_and_step(
        cfg, train.build_optimizer(args.optimizer, args.lr, args.warmup,
                                   args.steps),
        run.mesh, run.rules, shapes, seed=args.seed,
    )
    xla = []
    for b in batches:
        with dist.sharding_rules(run.mesh, run.rules):
            state, m = step_fn(state, b)
        xla.append(float(m["loss"]))
    diff = max(abs(a - b) for a, b in zip(run.losses, xla))
    print(f"[train] xla losses {xla}; max |dloss| kernel vs xla "
          f"{diff:.5f} (tolerance {LOSS_TOL})", flush=True)
    check(diff <= LOSS_TOL, f"kernel and xla losses differ by {diff}")
    print(f"[train] setup: {clock.since(mark)}; phase wall "
          f"{time.monotonic() - t0:.1f} s", flush=True)


# -- four chips -------------------------------------------------------------


def sharded_serve_phase(clock):
    from repro.configs import get_config  # noqa: PLC0415
    from repro.launch.mesh import make_serve_mesh  # noqa: PLC0415

    cfg = get_config(SERVE_ARCH)
    mark = clock.mark()
    params, prompts = serve_setup(cfg)
    ref, _ = serve(params, cfg, prompts)
    gc.collect()
    got, stats = serve(params, cfg, prompts, mesh=make_serve_mesh(2, 2))
    print(f"[serve-2x2] {len(got)} requests OK on the 2x2 serve mesh; "
          f"dispatch_failures=0 cache_rebuilds=0 quarantined=0; "
          f"dispatches={stats['dispatches']}", flush=True)
    logits = teacher_forced(params, cfg, prompts, ref)
    top2 = np.sort(logits, axis=-1)[..., -2:]
    margin = top2[..., 1] - top2[..., 0]
    same = 0
    for i, (a, b) in enumerate(zip(ref, got)):
        diff = np.flatnonzero(a != b)
        if diff.size == 0:
            same += 1
            continue
        j = int(diff[0])  # later tokens follow different prefixes
        check(margin[i, j] <= MARGIN_TOL,
              f"request {i}: sharded token {j} differs from one device at "
              f"margin {margin[i, j]:.3f} > {MARGIN_TOL}")
        print(f"[serve-2x2] request {i} forks at token {j}, margin "
              f"{margin[i, j]:.4f} <= {MARGIN_TOL}", flush=True)
    print(f"[serve-2x2] token parity with one device: {same}/{len(ref)} "
          f"requests identical; {clock.since(mark)}", flush=True)
    margin_check(teacher_forced(params, cfg, prompts, got), got,
                 "sharded engine vs kernel lm_apply")


def dp_train_phase(clock):
    mark = clock.mark()
    one = train_losses(TRAIN_ARGS + ["--mesh-data", "1"], 2).losses
    dp = train_losses(TRAIN_ARGS + ["--mesh-data", "4"], 2).losses
    diff = max(abs(a - b) for a, b in zip(one, dp))
    print(f"[train-4x1] one chip {one}; 4x1 data mesh {dp}; max |dloss| "
          f"{diff:.5f} (tolerance {LOSS_TOL}); {clock.since(mark)}",
          flush=True)
    check(diff <= LOSS_TOL, f"data-parallel losses differ by {diff}")


# -- driver -----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip paths, on four chips")
    args = ap.parse_args(argv)

    import jax  # noqa: PLC0415

    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("[device] no TPU found: nothing to check", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"[device] {want} chips needed, {len(devices)} found",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache  # noqa: PLC0415

    print(f"[device] compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    try:
        if args.four_chips:
            sharded_serve_phase(clock)
            dp_train_phase(clock)
        else:
            serve_phase(clock)
            train_phase(clock)
    except SmokeFailure as e:
        print(f"[fail] {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
