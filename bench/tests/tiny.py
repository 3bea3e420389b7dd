"""A benchmark of two tiny cells, written into a directory, for running the
harness end to end on the CPU (``cpu_cell.py``).

The cells are the real ones cut to a size a test can hold: the same
entries, generator, reference and readers, with model widths, engine
slots and traffic lengths cut down, and limits of their own.
"""

from __future__ import annotations

import copy
import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 128}
OVERRIDES = {"d_model": 64, "d_ff": 128, "n_groups": 2, "n_heads": 4,
             "n_kv_heads": 2, "vocab": 128, "attn_chunk": 16, "max_seq": 1024}

SERVE_LIMITS = {"mean_gap": 0.001}
TRAIN_LIMITS = {"loss_gap": 0.02, "grad_gap": 0.05, "change_gap": 0.05}


def _config(name: str, small_system: dict) -> dict:
    doc = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    doc = copy.deepcopy(doc)
    doc["model"].update(SMALL)
    doc["system"]["overrides"] = dict(doc["system"].get("overrides", {}), **OVERRIDES)
    doc["system"].update(small_system)
    return doc


def write(root: pathlib.Path, serve_limits=None, train_limits=None) -> pathlib.Path:
    """Writes the tiny benchmark under ``root``; returns its bench dir."""
    root = pathlib.Path(root)
    bench = root / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", bench / "metrics", dirs_exist_ok=True)
    serve = _config("qwen2-1.5b", {
        "engine": {"max_slots": 3, "n_max": 512, "decode_block": 4, "prefill_chunk": 64},
        "weights_dtype": "float32"})
    train = _config("smollm-135m", {"batch": 4, "seq": 64})
    for doc in (serve, train):
        doc["system"]["overrides"]["dtype"] = "float32"
    chat = json.loads((BENCH / "traffic" / "chat.json").read_text())
    chat.update(arrivals={"process": "poisson", "rate_per_s": 4.0},
                prompt={"dist": "lognormal", "median": 40, "sigma": 1.0, "min": 16,
                        "max": 160, "round_up": 16},
                output={"dist": "lognormal", "median": 10, "sigma": 0.8, "min": 4, "max": 24},
                grace_s=120)
    files = {
        "configs/tiny-serve.json": serve, "configs/tiny-train.json": train,
        "traffic/chat.json": chat,
        "traffic/train.json": json.loads((BENCH / "traffic" / "train.json").read_text()),
        "limits/tiny-serve.chat.json": serve_limits or SERVE_LIMITS,
        "limits/tiny-train.train.json": train_limits or TRAIN_LIMITS,
    }
    for rel, doc in files.items():
        (bench / rel).write_text(json.dumps(doc, indent=1))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names = {"qwen2-1.5b.chat": "tiny-serve.chat", "smollm-135m.train": "tiny-train.train"}
    configs = {"qwen2-1.5b": "tiny-serve", "smollm-135m": "tiny-train"}
    doc = copy.deepcopy(real)
    for w in doc["workloads"]:
        w["name"], w["config"] = names[w["name"]], configs[w["config"]]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [names[x] for x in m["workloads"]]
    for c in doc["configs"]:
        c["name"] = configs[c["name"]]
        c["file"] = f"bench/configs/{c['name']}.json"
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return bench
