"""Serving resilience: goodput & recovery latency under the standard
fault trace.

Runs the same 4-request workload three ways — fault-free, under the
standard seeded trace (queue flood + 1 dispatch failure + 1 NaN slot
corruption, ``serve.faults.standard_trace``), and the faulted run again
on a 2x2 host-CPU mesh — and reports:

  * ``resilience_clean``        — fault-free goodput (OK tokens/s) and
    block count, the baseline the faulted runs are judged against.
  * ``resilience_faulted``      — goodput under the trace, plus
    ``ok_identical`` (every OK output token-identical to the clean run —
    the ISSUE 6 acceptance claim), the shed/quarantined/retries counters,
    and ``recovery_blocks`` (decode blocks from quarantine to all user
    requests finishing — the quarantine-to-recovered latency).
  * ``resilience_faulted_2x2``  — the same trace on a 2x2 mesh (sharded
    health sweep + sharded corruption/clear), same acceptance claim.

Absolute tokens/s on host CPU is not the signal; the tracked numbers are
the goodput RATIO faulted/clean, ``ok_identical`` and ``recovery_blocks``.
Runs in a subprocess (``--xla_force_host_platform_device_count`` must be
set before jax import).  Rows are aggregated into
``BENCH_resilience.json`` by benchmarks/run.py.
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
    import jax, numpy as np
    from repro.configs import get_reduced
    from repro.models import lm_init
    from repro.serve import (Request, ServeEngine, ResiliencePolicy,
                             Status, standard_trace)
    from repro.launch.mesh import make_serve_mesh

    rng = np.random.default_rng(0)
    cfg = get_reduced("smollm-135m")
    params = lm_init(jax.random.PRNGKey(0), cfg)
    N_REQ, NEW_TOKENS = 4, 16
    prompts = [rng.integers(0, cfg.vocab, size=8).astype(np.int32)
               for _ in range(N_REQ)]

    def run(mesh, plan):
        eng = ServeEngine(params, cfg, max_slots=2, n_max=64,
                          decode_block=4, mesh=mesh, fault_plan=plan,
                          policy=ResiliencePolicy(max_queue=4))
        rids = [eng.submit(Request(tokens=p, max_new_tokens=NEW_TOKENS))
                for p in prompts]
        quarantine_block = None
        t0 = time.perf_counter()
        while eng.step():
            s = eng.stats()
            if quarantine_block is None and s.get("quarantined", 0):
                quarantine_block = s["blocks"]
        wall = time.perf_counter() - t0
        results = eng.run(return_results=True)
        stats = eng.stats()
        user = [results[r] for r in rids]
        good_tokens = sum(
            r.tokens.size for r in results.values()
            if r.status in (Status.OK, Status.DEGRADED))
        recovery = (stats["blocks"] - quarantine_block
                    if quarantine_block is not None else 0)
        return {
            "wall_s": wall,
            "good_tokens": int(good_tokens),
            "tokens": [r.tokens.tolist() for r in user],
            "all_terminal": all(r.status is not None for r in user),
            "recovery_blocks": int(recovery),
            "stats": {k: int(v) for k, v in stats.items()},
        }

    results = {}
    # Warm up both paths: plans are single-use, so each run gets a fresh
    # trace.  The faulted warmup compiles the recovery-only variants
    # (corrupt/clear/health + the continuation re-prefill lengths).
    run(None, None)
    run(None, standard_trace(slot=0, seed=0))
    clean = run(None, None)
    results["clean"] = clean
    faulted = run(None, standard_trace(slot=0, seed=0))
    faulted["ok_identical"] = faulted["tokens"] == clean["tokens"]
    results["faulted"] = faulted
    mesh = make_serve_mesh(2, 2)
    run(mesh, standard_trace(slot=0, seed=0))  # warmup sharded variants
    clean22 = run(mesh, None)
    results["clean_2x2"] = clean22
    f22 = run(mesh, standard_trace(slot=0, seed=0))
    f22["ok_identical"] = f22["tokens"] == clean["tokens"]
    results["faulted_2x2"] = f22
    print("BENCH_JSON:" + json.dumps(results))
"""


def run():
    """Executes the resilience workload in a multi-device subprocess and
    emits the clean/faulted/faulted-2x2 rows (see module docstring).

    Returns:
      List of ``name,us,derived`` CSV row strings for run.py aggregation.
    """
    refuse_on_tpu("bench_resilience")
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(_REPO / "src"),
           "PATH": os.environ.get("PATH", "/usr/bin:/bin:/usr/local/bin"),
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_CHILD)],
        capture_output=True, text=True, timeout=1200, env=env, cwd=str(_REPO),
    )
    if out.returncode != 0:
        raise RuntimeError(f"bench_resilience subprocess failed: "
                           f"{out.stderr[-2000:]}")
    payload = [ln for ln in out.stdout.splitlines()
               if ln.startswith("BENCH_JSON:")][-1]
    r = json.loads(payload[len("BENCH_JSON:"):])

    rows = []
    clean = r["clean"]
    goodput_clean = clean["good_tokens"] / clean["wall_s"]
    rows.append(emit(
        "resilience_clean", clean["wall_s"] * 1e6,
        f"goodput_tok_s={goodput_clean:.1f};"
        f"blocks={clean['stats']['blocks']}",
    ))
    goodput_22 = r["clean_2x2"]["good_tokens"] / r["clean_2x2"]["wall_s"]
    # each faulted run is judged against its own mesh's clean baseline, so
    # the ratio isolates fault-handling overhead from mesh overhead
    for key, name, base in (
        ("faulted", "resilience_faulted", goodput_clean),
        ("faulted_2x2", "resilience_faulted_2x2", goodput_22),
    ):
        f = r[key]
        s = f["stats"]
        goodput = f["good_tokens"] / f["wall_s"]
        rows.append(emit(
            name, f["wall_s"] * 1e6,
            f"goodput_tok_s={goodput:.1f};"
            f"goodput_ratio={goodput / base:.2f};"
            f"ok_identical={f['ok_identical']};"
            f"all_terminal={f['all_terminal']};"
            f"recovery_blocks={f['recovery_blocks']};"
            f"shed={s.get('shed', 0)};"
            f"quarantined={s.get('quarantined', 0)};"
            f"retries={s.get('retries', 0)};"
            f"dispatch_retries={s.get('dispatch_retries', 0)}",
        ))
    return rows


if __name__ == "__main__":
    run()
