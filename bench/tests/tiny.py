"""A benchmark of tiny cells, written into a directory, for running the
harness end to end on the CPU (``cpu_cell.py``).

The cells are the real ones of ``BENCHMARK.json`` cut to a size a test
can hold: the same entries, generator, reference and readers, with model
widths, engine slots and traffic lengths cut down, and limits of their
own.  Each configuration's cut is ``tiny_cuts/configs/<config>.json``
(``name``, the tiny configuration's name; ``system``, its settings;
optionally ``model`` and ``overrides`` beyond ``SMALL`` and ``OVERRIDES``),
and each traffic mix's is ``tiny_cuts/traffic/<mix>.json`` (the keys of
the mix it replaces).  A cell whose configuration or mix has no cut is
refused, so a cell added by files alone brings its cut beside them.
"""

from __future__ import annotations

import copy
import json
import os
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
CUTS = pathlib.Path(__file__).resolve().parent / "tiny_cuts"

SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 128}
OVERRIDES = {"d_model": 64, "d_ff": 128, "n_groups": 2, "n_heads": 4,
             "n_kv_heads": 2, "vocab": 128, "attn_chunk": 16, "max_seq": 1024,
             "dtype": "float32"}

SERVE_LIMITS = {"mean_gap": 0.001}
TRAIN_LIMITS = {"loss_gap": 0.02, "grad_gap": 0.05, "change_gap": 0.05}
# the faults each entry's cells can have, besides the control (cpu_cell.py)
FAULTS = {"serve": ("token", "control"), "train": ("unchanged", "half", "control")}


def cut(kind: str, name: str, workload: str) -> dict:
    """The tiny cut of configuration or mix ``name`` that cell ``workload``
    uses; a missing cut is refused, naming the cell and the file."""
    path = CUTS / kind / f"{name}.json"
    if not path.exists():
        raise KeyError(f"cell {workload} has no tiny cut: add {os.path.relpath(path, BENCH.parent)}")
    return json.loads(path.read_text())


def _config(name: str, workload: str) -> tuple[str, dict]:
    small = cut("configs", name, workload)
    doc = copy.deepcopy(json.loads((BENCH / "configs" / f"{name}.json").read_text()))
    doc["model"].update(SMALL, **small.get("model", {}))
    doc["system"]["overrides"] = {**doc["system"].get("overrides", {}), **OVERRIDES,
                                  **small.get("overrides", {})}
    doc["system"].update(small["system"])
    return small["name"], doc


def _plan() -> tuple[dict, dict, dict, dict]:
    """``BENCHMARK.json``; real cell name -> tiny cell name; real
    configuration name -> tiny (name, document); mix name -> tiny mix."""
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names, configs, mixes = {}, {}, {}
    for w in real["workloads"]:
        if w["config"] not in configs:
            configs[w["config"]] = _config(w["config"], w["name"])
        if w["traffic"] not in mixes:
            mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
            mixes[w["traffic"]] = dict(mix, **cut("traffic", w["traffic"], w["name"]))
        names[w["name"]] = f"{configs[w['config']][0]}.{w['traffic']}"
    return real, names, configs, mixes


def cells() -> list[tuple[str, str]]:
    """(tiny cell, entry) for every cell of ``BENCHMARK.json``, in its order."""
    real, names, configs, _ = _plan()
    return [(names[w["name"]], configs[w["config"]][1]["system"]["entry"])
            for w in real["workloads"]]


def write(root: pathlib.Path, serve_limits=None, train_limits=None) -> pathlib.Path:
    """Writes the tiny benchmark under ``root``; returns its bench dir."""
    root = pathlib.Path(root)
    bench = root / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "reference"):
        shutil.copytree(BENCH / sub, bench / sub, dirs_exist_ok=True)
    real, names, configs, mixes = _plan()
    limits = {"serve": serve_limits or SERVE_LIMITS, "train": train_limits or TRAIN_LIMITS}
    files = {f"traffic/{mix}.json": doc for mix, doc in mixes.items()}
    for name, doc in configs.values():
        files[f"configs/{name}.json"] = doc
    for w in real["workloads"]:
        files[f"limits/{names[w['name']]}.json"] = limits[configs[w["config"]][1]["system"]["entry"]]
    for rel, doc in files.items():
        (bench / rel).write_text(json.dumps(doc, indent=1))
    doc = copy.deepcopy(real)
    for w in doc["workloads"]:
        w["name"], w["config"] = names[w["name"]], configs[w["config"]][0]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [names[x] for x in m["workloads"]]
    for c in doc["configs"]:
        c["name"] = configs[c["name"]][0]
        c["file"] = f"bench/configs/{c['name']}.json"
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return bench
