"""Readings that the limits of ``bench/limits/<workload>.json`` are set
from: the program's compared numbers on many seeds, and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

All seeds run in one process (set-up compiles once).  For each seed the
cell runs as ``bench/run.py`` runs it, and then:

* a served cell also reads its control, the reference with float8
  weights put in the program's place: at each position of the same
  prompts and served tokens, the float32 reference's gap of the token
  that the float8 reference puts first (``control_mean_gap``, and the
  widest, ``control_max_gap``); ``--engine state_dtype=int8`` runs the
  program with its own lower-precision moment state instead;
* a training cell also reads its control, the reference in bfloat16
  (weights, activations and updates) in the program's place, and the
  fault of half of each batch left out, planted in the reference, both
  against the float32 reference.

``--rates r1,r2,..`` sweeps a served cell instead: the first seed at each
offered rate, with its end-to-end metrics and the queue at the window's
close, to find the highest rate the engine sustains.

``--memory b1,b2,..`` compiles a training cell's step at each batch size
on the chip, prints its ``memory_analysis``, runs it once and prints the
chip's peak bytes in use.

Prints one JSON object per seed (or rate).
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def serve_control(run) -> dict:
    import numpy as np

    from bench.entries import serve

    recs = run.data["records"]
    _, lower = serve.reference_gaps(run, run.data["params"], recs, serve.checked(recs),
                                    dtype=run.reference.FP8)
    low = np.concatenate(lower)
    return {"control_mean_gap": float(low.mean()), "control_max_gap": float(low.max())}


def served(run) -> dict:
    """What a served window did beyond its end-to-end metrics: output
    tokens per second completed in the window, and the median and 90th
    percentile of time to first token, from each request's due time."""
    from bench.generator import percentile

    end, recs = run.data["t_end"], run.data["records"]
    first = sum(1 for r in recs if r["first"] is not None and r["first"] <= end)
    decode = sum(s["decode_tokens"] for s in run.data["steps"] if s["t1"] <= end)
    ttft = [(r["first"] or run.data["grace_end"]) - r["due"] for r in recs]
    return {"out_tok_s": (first + decode) / (end - run.data["t0"]),
            "ttft_p50_ms": 1e3 * percentile(ttft, 50), "ttft_p90_ms": 1e3 * percentile(ttft, 90),
            "requests": len(recs)}


def train_control(run, task) -> dict:
    import jax.numpy as jnp

    from bench.entries import train

    steps = train.FIRST_STEPS
    full = run.data["reference"]
    out = {}
    for name, kw in (("control", {"dtype": jnp.bfloat16}),
                     ("half_batch", {"rows": run.data["batch"] // 2})):
        got = train.compare(*train.reference_steps(run, task, steps, **kw), full)
        out.update({f"{name}_{k}": got[k] for k in ("loss_gap", "grad_gap", "change_gap")})
    return out


def train_memory(config: dict, batches) -> None:
    import jax
    import jax.numpy as jnp

    from bench.entries.common import model_config
    from bench.entries.train import optimizer
    from repro.distributed import api as dist
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import make_sharded_state_and_step

    sysd = config["system"]
    cfg = model_config(config)
    mesh = make_host_mesh(1, 1)
    rules = dist.rules_for_mesh(mesh)
    for b in batches:
        shapes = {k: jax.ShapeDtypeStruct((b, sysd["seq"]), jnp.int32) for k in ("tokens", "labels")}
        state, step_fn, _, batch_ns = make_sharded_state_and_step(
            cfg, optimizer(sysd["optimizer"]), mesh, rules, shapes)
        batch = {k: jax.device_put(jnp.zeros((b, sysd["seq"]), jnp.int32), batch_ns[k])
                 for k in shapes}
        with dist.sharding_rules(mesh, rules):
            ma = step_fn.lower(state, batch).compile().memory_analysis()
            state, m = step_fn(state, batch)
            jax.block_until_ready(state)
        gib = lambda x: round(x / 2**30, 3)
        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            "batch": b, "seq": sysd["seq"],
            "arguments_gib": gib(ma.argument_size_in_bytes), "outputs_gib": gib(ma.output_size_in_bytes),
            "temporaries_gib": gib(ma.temp_size_in_bytes), "aliased_gib": gib(ma.alias_size_in_bytes),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"), "bytes_limit": stats.get("bytes_limit"),
            "pallas_calls": step_fn.lower(state, batch).as_text().count("tpu_custom_call"),
        }), flush=True)
        del state, m, batch


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--engine", action="append", default=[],
                    help="key=value: an engine setting of a served cell to override")
    ap.add_argument("--memory", default=None)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.catalog import Catalog
    from bench.entries import serve, train

    cat = Catalog()
    cell = cat.workload(args.workload)
    config, traffic = cat.config(cell["config"]), cat.traffic(cell["traffic"])
    limits = {k: float("inf") for k in cat.limits(args.workload)}
    for kv in args.engine:
        key, value = kv.split("=", 1)
        engine = dict(config["system"]["engine"], **{key: int(value) if value.isdigit() else value})
        config = dict(config, system=dict(config["system"], engine=engine))
    harness.configure_jax_cache()
    devices = harness.find_devices(cell["chips"])
    if devices is None:
        return harness.NO_DEVICE
    from bench import work

    if args.memory:
        train_memory(config, [int(b) for b in args.memory.split(",")])
        return 0
    clock = harness.CompileClock()
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None] * len(seeds)
    for seed, rate in zip([seeds[0]] * len(rates) if args.rates else seeds, rates):
        t = time.monotonic()
        if rate is not None:
            traffic = dict(traffic, arrivals=dict(traffic["arrivals"], rate_per_s=rate))
        run = harness.Run(workload=cell, config=config, traffic=traffic, seed=seed,
                          seconds=args.seconds, peak=work.peaks(devices[0].device_kind),
                          reference=cat.reference(config))
        entry = serve if config["system"]["entry"] == "serve" else train
        entry.run_cell(run, limits, clock, harness.Tracer(False), time.monotonic(), devices)
        row = {"seed": seed, "failed": run.failed, "attempted": run.attempted,
               "window_s": run.window_s, "setup_s": run.setup_s,
               **{c.name: c.value for c in run.checks}}
        if entry is serve:
            row.update(served(run))
            row.update({m["name"]: cat.reader(m["name"])(run)
                        for m in cat.metrics(args.workload, per_layer=False)})
        if rate is not None:
            row.update(rate=rate, queue_at_close=[s["queue"] for s in run.data["steps"]
                                                  if s["t1"] <= run.data["t_end"]][-1:])
        elif entry is serve:
            row.update(serve_control(run))
        elif entry is train:
            row.update(train_control(run, run.data["task"]))
        row["seconds"] = time.monotonic() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
