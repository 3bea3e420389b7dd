import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture × its input shape) cell and mesh, this lowers and
compiles the real step function with ShapeDtypeStruct inputs (zero
allocation), prints ``memory_analysis()`` / ``cost_analysis()``, parses
collective bytes from the optimized HLO, and writes one JSON artifact per
cell under artifacts/dryrun/ (resumable: existing artifacts are skipped
unless --force).

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh pod            # 40-cell sweep
  python -m repro.launch.dryrun --all --mesh multipod       # 2×16×16
  python -m repro.launch.dryrun --all --backend softmax     # arch baselines

NOTE: the XLA_FLAGS assignment above MUST stay the first statement — jax
locks the device count on first init.  Never import this module from tests.
"""

import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.flops import count_fn
from repro.analysis.roofline import TPUV5E, collective_bytes, roofline_report
from repro.configs import ARCHS, SHAPES, applicable_shapes, get_config, input_specs
from repro.distributed import api as dist
from repro.distributed.sharding import (
    batch_specs,
    cache_specs,
    named_shardings,
    opt_state_specs,
    param_specs,
)
from repro.launch.mesh import make_production_mesh
from repro.models import lm_init
from repro.models.config import ModelConfig, count_active_params, count_params
from repro.models.lm import (
    lm_decode_step,
    lm_init_caches,
    lm_prefill,
    lm_state_bytes,
)
from repro.optim import adafactor, adamw, cosine_warmup
from repro.train.step import TrainState, make_train_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")


def training_preset(cfg: ModelConfig, n_params: int):
    """Optimizer + numerics preset by scale (see DESIGN.md memory budget)."""
    sched = cosine_warmup(3e-4, 2000, 100000)
    if n_params > 100e9:
        # 1T-class: bf16 params + classic adafactor (no momentum, factored v)
        return cfg.replace(param_dtype="bfloat16"), adafactor(sched, momentum=None)
    if n_params > 5e9:
        return cfg.replace(param_dtype="bfloat16"), adamw(sched)
    return cfg, adamw(sched)


def rules_for(cfg: ModelConfig, mesh, n_params: int, variant=None):
    over = {}
    if "pod" in mesh.axis_names and n_params > 100e9:
        over["fsdp"] = ("pod", "data")  # ZeRO across pods for 1T-class
    if variant == "dp_only":
        # §Perf cell A: sub-1B models waste the TP axis — run pure DP over
        # the whole mesh (params replicated, one grad all-reduce).
        axes = tuple(mesh.axis_names)
        over = {"dp": axes, "fsdp": None, "tp": None, "ep": None, "sp": None}
    if variant == "fsdp_cp":
        # §Perf cell C iteration 2: no TP — params fully sharded (ZeRO-3,
        # gathered per layer), sequence sharded over the former TP axis,
        # attention via context parallelism (state exchange), MLP token-local.
        # Exchanging O(params/L) weights beats exchanging O(b·n·d)
        # activations whenever b·n·d per layer > param bytes per layer.
        axes = tuple(mesh.axis_names)
        over = {"dp": "data" if "pod" not in axes else ("pod", "data"),
                "fsdp": axes, "tp": None, "ep": "model", "sp": "model"}
    return dist.rules_for_mesh(mesh, **over)


# --variant presets: config/rules deltas measured against the baselines
VARIANTS = {
    "dp_only": {},                       # rules change only (see rules_for)
    "cp_attn": {"attn_sharding": "cp"},  # §Perf cell C: CP taylor attention
    "moe_int8": {},                      # cf 1.0 + int8 a2a (applied below)
    "sym_state": {},                     # symmetric-compressed second moments
    "fsdp_cp": {"attn_sharding": "cp"},  # ZeRO-3 + CP attention, no TP
}


def _eval_shape_tree(fn, *args):
    return jax.eval_shape(fn, *args)


def lower_cell(arch: str, shape: str, mesh, backend=None, donate=True, save_hlo=False,
               overrides=None, variant=None):
    """Lower + compile one cell.  Returns (record dict, compiled)."""
    over = dict(VARIANTS.get(variant, {}))
    over.update(overrides or {})
    cfg = get_config(arch, backend=backend, **over)
    if variant == "moe_int8" and cfg.moe is not None:
        import dataclasses as _dc

        cfg = cfg.replace(
            moe=_dc.replace(cfg.moe, capacity_factor=1.0, a2a_quant="int8")
        )
    if variant == "sym_state":
        import dataclasses as _dc

        cfg = cfg.replace(taylor=_dc.replace(cfg.taylor, sym_state=True))
    if shape == "long_500k" and not cfg.supports_long_context:
        raise ValueError(
            "long_500k requires O(1)-state decode (registry state_kind != 'kv')"
        )
    n_params = count_params(cfg)
    n_active = count_active_params(cfg)
    spec = SHAPES[shape]
    rules = rules_for(cfg, mesh, n_params, variant=variant)
    key = jax.ShapeDtypeStruct((2,), "uint32")

    if spec.kind == "train":
        cfg, opt = training_preset(cfg, n_params)
        step = make_train_step(cfg, opt)
        pshapes = _eval_shape_tree(lambda k: lm_init(k, cfg), key)
        oshapes = _eval_shape_tree(opt.init, pshapes)
        state_shapes = TrainState(
            step=jax.ShapeDtypeStruct((), "int32"), params=pshapes, opt_state=oshapes
        )
        pspecs = param_specs(pshapes, mesh, rules)
        ospecs = opt_state_specs(oshapes, pspecs, pshapes, mesh, rules)
        state_specs = TrainState(step=P(), params=pspecs, opt_state=ospecs)
        batch_shapes = input_specs(cfg, shape)
        bspecs = batch_specs(batch_shapes, mesh, rules)
        state_ns = named_shardings(state_specs, mesh)
        batch_ns = named_shardings(bspecs, mesh)
        metrics_ns = {
            "loss": NamedSharding(mesh, P()),
            "aux_loss": NamedSharding(mesh, P()),
            "total_loss": NamedSharding(mesh, P()),
        }
        fn = jax.jit(
            step,
            in_shardings=(state_ns, batch_ns),
            out_shardings=(state_ns, metrics_ns),
            donate_argnums=(0,) if donate else (),
        )
        args = (state_shapes, batch_shapes)
        model_flops = 6.0 * n_active * spec.batch * spec.seq

    elif spec.kind == "prefill":
        pshapes = _eval_shape_tree(lambda k: lm_init(k, cfg), key)
        pspecs = param_specs(pshapes, mesh, rules)
        batch_shapes = input_specs(cfg, shape)
        bspecs = batch_specs(batch_shapes, mesh, rules)
        n_max = spec.seq
        fwd = functools.partial(lm_prefill, cfg=cfg, n_max=n_max)
        cshapes = _eval_shape_tree(lambda p, b: fwd(p, b)[1], pshapes, batch_shapes)
        cspecs = cache_specs(cshapes, mesh, rules, spec.batch)
        logits_ns = NamedSharding(mesh, P(rules.get("dp"), None))
        fn = jax.jit(
            fwd,
            in_shardings=(named_shardings(pspecs, mesh), named_shardings(bspecs, mesh)),
            out_shardings=(logits_ns, named_shardings(cspecs, mesh)),
        )
        args = (pshapes, batch_shapes)
        model_flops = 2.0 * n_active * spec.batch * spec.seq

    elif spec.kind == "decode":
        pshapes = _eval_shape_tree(lambda k: lm_init(k, cfg), key)
        pspecs = param_specs(pshapes, mesh, rules)
        b = spec.batch
        dt = jnp.dtype(cfg.dtype)
        cshapes = _eval_shape_tree(
            lambda: lm_init_caches(cfg, b, spec.seq, dt)
        )
        cspecs = cache_specs(cshapes, mesh, rules, b)
        tok = jax.ShapeDtypeStruct((b,), "int32")
        pos = jax.ShapeDtypeStruct((), "int32")
        tok_spec = batch_specs(tok, mesh, rules)
        step_fn = functools.partial(lm_decode_step, cfg=cfg)
        logits_ns = NamedSharding(mesh, P(tok_spec[0], None))
        fn = jax.jit(
            step_fn,
            in_shardings=(
                named_shardings(pspecs, mesh),
                NamedSharding(mesh, tok_spec),
                named_shardings(cspecs, mesh),
                NamedSharding(mesh, P()),
            ),
            out_shardings=(logits_ns, named_shardings(cspecs, mesh)),
            donate_argnums=(2,) if donate else (),
        )
        args = (pshapes, tok, cshapes, pos)
        model_flops = 2.0 * n_active * spec.batch
        # per-slot persistent state, summed per layer — a hybrid schedule mixes
        # O(1) moment blocks with O(window) KV rings so no single-backend
        # formula is valid here.
        decode_state_bytes = lm_state_bytes(cfg, b, spec.seq, dt)
    else:
        raise ValueError(spec.kind)

    t0 = time.monotonic()
    with dist.sharding_rules(mesh, rules):
        lowered = fn.lower(*args)
        # trip-exact global flops/bytes (jaxpr walker; see analysis/flops)
        if spec.kind == "train":
            walker = count_fn(step, *args)
        elif spec.kind == "prefill":
            walker = count_fn(fwd, *args)
        else:
            walker = count_fn(step_fn, *args)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
        print(f"[dryrun] memory_analysis: {mem}")
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0]
    cost = {k: float(v) for k, v in cost.items() if isinstance(v, (int, float))}
    print(f"[dryrun] cost_analysis: flops={cost.get('flops', 0):.3e} "
          f"bytes={cost.get('bytes accessed', 0):.3e}")
    hlo = compiled.as_text()
    n_chips = mesh.devices.size
    report = roofline_report(
        cost, hlo, n_chips, TPUV5E, model_flops=model_flops, walker=walker
    )
    # bytes per device that must persist in HBM (params+opt+caches live in args)
    args_b = mem.get("argument_size_in_bytes", 0)
    temp_b = mem.get("temp_size_in_bytes", 0)
    out_b = mem.get("output_size_in_bytes", 0)
    alias_b = mem.get("alias_size_in_bytes", 0)
    peak = args_b + temp_b + out_b - alias_b
    record = {
        "arch": arch,
        "shape": shape,
        # per-layer description under a hybrid schedule ("taylor+softmax_window")
        "backend": cfg.backend_desc if not cfg.is_attention_free else "ssm",
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_chips": n_chips,
        "n_params": n_params,
        "n_active_params": n_active,
        "memory": mem,
        "hbm_peak_bytes_per_chip": peak,
        "fits_hbm": bool(peak <= TPUV5E.hbm_bytes),
        "cost": cost,
        "roofline": report,
        "lower_s": t_lower,
        "compile_s": t_compile,
    }
    if spec.kind == "decode":
        record["decode_state_bytes"] = decode_state_bytes
    if save_hlo:
        record["hlo_path"] = _save_hlo(arch, shape, record["mesh"], hlo)
    return record, compiled


def _save_hlo(arch, shape, mesh_name, hlo):
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    path = os.path.join(ARTIFACT_DIR, f"{arch}_{shape}_{mesh_name}.hlo.txt")
    with open(path, "w") as f:
        f.write(hlo)
    return path


def cell_path(arch, shape, mesh_name, backend, variant=None):
    tag = f"_{backend}" if backend else ""
    if variant:
        tag += f"_{variant}"
    return os.path.join(ARTIFACT_DIR, f"{arch}_{shape}_{mesh_name}{tag}.json")


def run_cell(arch, shape, mesh, backend=None, force=False, save_hlo=False, variant=None):
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    path = cell_path(arch, shape, mesh_name, backend, variant)
    if os.path.exists(path) and not force:
        print(f"[dryrun] skip (exists): {path}")
        return json.load(open(path))
    print(f"[dryrun] === {arch} × {shape} × mesh {mesh_name}"
          + (f" × {backend}" if backend else "")
          + (f" × {variant}" if variant else "") + " ===")
    try:
        record, _ = lower_cell(arch, shape, mesh, backend=backend,
                               save_hlo=save_hlo, variant=variant)
        record["status"] = "ok"
        record["variant"] = variant
    except Exception as e:
        record = {
            "arch": arch, "shape": shape, "mesh": mesh_name, "backend": backend,
            "status": "error", "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        print(f"[dryrun] FAILED: {record['error']}")
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=2, default=str)
    if record["status"] == "ok":
        r = record["roofline"]
        print(f"[dryrun] {arch}×{shape}: compute={r['compute_s']:.4f}s "
              f"memory={r['memory_s']:.4f}s collective={r['collective_s']:.4f}s "
              f"dominant={r['dominant']} fits_hbm={record['fits_hbm']} "
              f"(compile {record['compile_s']:.1f}s)")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("pod", "multipod"), default="pod")
    ap.add_argument("--backend", choices=("softmax", "taylor", "linear_elu"))
    ap.add_argument("--all", action="store_true", help="sweep all applicable cells")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--variant", choices=list(VARIANTS))
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))
    print(f"[dryrun] mesh {mesh.devices.shape} axes {mesh.axis_names} "
          f"({mesh.devices.size} devices)")

    if args.all:
        ok = failed = 0
        for arch in ARCHS:
            cfg = get_config(arch, backend=args.backend)
            for shape in applicable_shapes(cfg):
                rec = run_cell(arch, shape, mesh, backend=args.backend,
                               force=args.force, save_hlo=args.save_hlo)
                ok += rec["status"] == "ok"
                failed += rec["status"] != "ok"
        print(f"[dryrun] sweep done: {ok} ok, {failed} failed")
        raise SystemExit(1 if failed else 0)

    if not (args.arch and args.shape):
        ap.error("--arch and --shape required (or --all)")
    rec = run_cell(args.arch, args.shape, mesh, backend=args.backend,
                   force=args.force, save_hlo=args.save_hlo, variant=args.variant)
    raise SystemExit(0 if rec["status"] == "ok" else 1)


if __name__ == "__main__":
    main()
