"""Mesh-sharded serving: the sharded engine vs the single-device engine.

Runs in a SUBPROCESS with ``--xla_force_host_platform_device_count`` (the
parent process must keep seeing one device; XLA_FLAGS is read at jax
import).  On emulated CPU devices the absolute tokens/sec is not the
signal — the tracked numbers are:

  * ``serve_sharded_decode_tp`` / ``serve_sharded_decode_slots`` —
    decode wall time of the full engine loop on a 1×2 tensor-parallel and
    a 2×1 slot-sharded mesh, with ``tokens_match=True`` asserting
    token-identical output to the single-device engine (the parity claim
    of tests/test_serve_sharded.py, tracked per PR), and
    ``dispatches_per_token`` from the engine's dispatch counters — the
    scheduler-efficiency number that stays meaningful when host-CPU wall
    time is noise.
  * ``serve_sharded_single_ref`` — the same workload on the degenerate
    single-device path, for the overhead ratio.
  * ``serve_prefill_chunked`` — chunked long-prompt prefill vs
    whole-prompt prefill: wall time ratio, dispatch count, and
    ``max_logit_diff`` (must sit in fp32 noise).

Rows are aggregated into ``BENCH_serve_sharded.json`` by
benchmarks/run.py (schema in README.md §Benchmarks).
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap

from benchmarks.common import emit, refuse_on_tpu

_REPO = pathlib.Path(__file__).resolve().parent.parent

_CHILD = """
    import time, json
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_reduced
    from repro.models import lm_init
    from repro.serve import Request, ServeEngine, prefill_chunked
    from repro.launch.mesh import make_serve_mesh

    rng = np.random.default_rng(0)
    cfg = get_reduced("qwen2-1.5b")  # taylor backend
    params = lm_init(jax.random.PRNGKey(0), cfg)
    N_STREAMS, NEW_TOKENS, N_MAX = 4, 24, 128
    prompts = [np.asarray(rng.integers(0, cfg.vocab, (int(n),)), np.int32)
               for n in rng.integers(8, 33, N_STREAMS)]

    def run_engine(mesh):
        eng = ServeEngine(params, cfg, max_slots=N_STREAMS, n_max=N_MAX,
                          decode_block=8, mesh=mesh)
        for p in prompts:
            eng.submit(Request(tokens=p, max_new_tokens=NEW_TOKENS))
        eng._admit()
        jax.block_until_ready(eng.caches)
        t0 = time.perf_counter()
        while eng.step():
            pass
        s = eng.stats()
        return {"seconds": time.perf_counter() - t0,
                "dispatches": s["dispatches"]}

    def run_tokens(mesh):
        eng = ServeEngine(params, cfg, max_slots=N_STREAMS, n_max=N_MAX,
                          decode_block=8, mesh=mesh)
        rids = [eng.submit(Request(tokens=p, max_new_tokens=NEW_TOKENS))
                for p in prompts]
        outs = eng.run()
        return [outs[r].tolist() for r in rids]

    results = {}
    ref_tokens = run_tokens(None)
    run_engine(None)  # warmup/jit
    results["single"] = run_engine(None)
    for name, shape in (("tp", (1, 2)), ("slots", (2, 1))):
        mesh = make_serve_mesh(*shape)
        toks = run_tokens(mesh)
        run_engine(mesh)  # warmup/jit
        results[name] = run_engine(mesh)
        results[name].update(
            tokens_match=toks == ref_tokens,
            mesh="x".join(map(str, shape)),
        )

    # chunked long-prompt prefill vs whole prefill (single device, both
    # through their jitted entry points, warmed up)
    from repro.serve.engine import _jitted_prefill
    long_prompt = jnp.asarray(rng.integers(0, cfg.vocab, (1, 96)), jnp.int32)
    whole_fn = _jitted_prefill(cfg, N_MAX)
    lw, _ = whole_fn(params, {"tokens": long_prompt})
    lc, _ = prefill_chunked(params, {"tokens": long_prompt}, cfg,
                            n_max=N_MAX, chunk=16)
    t0 = time.perf_counter()
    whole_fn(params, {"tokens": long_prompt})[0].block_until_ready()
    t_whole = time.perf_counter() - t0
    t0 = time.perf_counter()
    prefill_chunked(params, {"tokens": long_prompt}, cfg,
                    n_max=N_MAX, chunk=16)[0].block_until_ready()
    t_chunk = time.perf_counter() - t0
    results["prefill"] = {
        "whole_s": t_whole, "chunked_s": t_chunk,
        "dispatches": 96 // 16,
        "max_logit_diff": float(jnp.max(jnp.abs(lw - lc))),
    }
    print("BENCH_JSON:" + json.dumps(results))
"""


def run():
    refuse_on_tpu("bench_serve_sharded")
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(_REPO / "src"),
           "PATH": os.environ.get("PATH", "/usr/bin:/bin:/usr/local/bin"),
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_CHILD)],
        capture_output=True, text=True, timeout=1200, env=env, cwd=str(_REPO),
    )
    if out.returncode != 0:
        raise RuntimeError(f"bench_serve_sharded subprocess failed: "
                           f"{out.stderr[-2000:]}")
    payload = [ln for ln in out.stdout.splitlines()
               if ln.startswith("BENCH_JSON:")][-1]
    r = json.loads(payload[len("BENCH_JSON:"):])

    rows = []
    total = 4 * 24
    t_single = r["single"]["seconds"]
    # dispatches-per-token makes the fewer-fatter-dispatches work
    # machine-checkable: the counter moves when scheduling changes, even
    # when host-CPU wall time is noise
    dpt_single = r["single"]["dispatches"] / total
    rows.append(emit(
        "serve_sharded_single_ref", t_single * 1e6,
        f"tok_s={total / t_single:.1f};mesh=1x1;"
        f"dispatches_per_token={dpt_single:.3f}",
    ))
    for name in ("tp", "slots"):
        t = r[name]["seconds"]
        rows.append(emit(
            f"serve_sharded_decode_{name}", t * 1e6,
            f"tok_s={total / t:.1f};mesh={r[name]['mesh']};"
            f"tokens_match={r[name]['tokens_match']};"
            f"overhead_vs_single={t / t_single:.2f};"
            f"dispatches_per_token={r[name]['dispatches'] / total:.3f}",
        ))
    p = r["prefill"]
    rows.append(emit(
        "serve_prefill_chunked", p["chunked_s"] * 1e6,
        f"whole_us={p['whole_s'] * 1e6:.1f};dispatches={p['dispatches']};"
        f"ratio_vs_whole={p['chunked_s'] / p['whole_s']:.2f};"
        f"max_logit_diff={p['max_logit_diff']:.2e}",
    ))
    return rows


if __name__ == "__main__":
    run()
