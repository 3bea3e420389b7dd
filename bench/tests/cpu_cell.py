"""Drives one run of a tiny cell on the CPU, past the harness's look for a
chip, optionally with the timed path broken underneath; prints the
result object.

    python cpu_cell.py <root> <workload> <seed> <seconds> [fault]

Faults: ``token`` (the engine's first token of each request altered where
it is sampled), ``unchanged`` (the train step returns its state
unchanged), ``half`` (the train step sees half of each batch).
``control`` puts the control in the program's place: the plain reference
in the nearest precision below the configuration's (serving: float8
weights; training: bfloat16), whose tokens or losses, gradients and
updates the check then judges.
"""

import json
import pathlib
import sys
import time

T_START = time.monotonic()
REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def break_path(fault: str) -> None:
    if fault == "token":
        from repro.serve import scheduler

        sample = scheduler.sample_tokens

        def altered(logits, *a, **k):
            return (sample(logits, *a, **k) + 1) % logits.shape[-1]

        scheduler.sample_tokens = altered
    elif fault in ("unchanged", "half"):
        from repro.launch import train

        make = train.make_train_step

        def broken(cfg, opt, *a, **k):
            step = make(cfg, opt, *a, **k)

            def f(state, batch):
                if fault == "half":
                    return step(state, {n: v[: v.shape[0] // 2] for n, v in batch.items()})
                return state, step(state, batch)[1]

            return f

        train.make_train_step = broken
    elif fault == "control":
        from bench import control
        from bench.entries import serve, train
        from bench.harness import Check

        def serve_check(run, params, records, limits):
            gap = control.serve_control(run)["control_mean_gap"]
            return [Check("mean_gap", gap, limits["mean_gap"])]

        def train_check(run, task, losses, grad_norms, change_norms, limits):
            run.data["reference"] = train.reference_steps(run, task, len(losses))
            got = control.train_control(run, task)
            return [Check(k, got[f"control_{k}"], limits[k])
                    for k in ("loss_gap", "grad_gap", "change_gap")]

        serve.check, train.check = serve_check, train_check
    elif fault:
        raise ValueError(f"unknown fault {fault!r}")


def main(root, workload, seed, seconds, fault=""):
    from bench import harness
    from bench.catalog import Catalog

    root = pathlib.Path(root)
    harness.CACHE_DIR = root / ".jax_cache"
    harness.TRACE_DIR = root / ".bench_trace"
    break_path(fault)
    args = harness.parse_args(["--workload", workload, "--seed", seed, "--seconds", seconds])
    result = harness.execute(args, Catalog(root, root / "bench"), T_START, require_tpu=False)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
