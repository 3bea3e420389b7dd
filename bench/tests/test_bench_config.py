"""What a configuration file decides: the program's config, checked key by
key against the file's ``model`` block, and the plain reference the
checks run."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.catalog import BENCH_DIR, Catalog
from bench.entries.common import COMPARED, model_config
from bench.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = sorted(p.stem for p in (BENCH_DIR / "configs").glob("*.json"))


def _doc(name: str) -> dict:
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_every_key_of_a_config_is_compared_or_listed(name):
    doc = _doc(name)
    cfg = model_config(doc)
    listed = set(doc.get("not_compared", {}))
    assert set(doc["model"]) <= set(COMPARED) | listed
    assert all(reason for reason in doc.get("not_compared", {}).values())
    assert cfg.d_model == doc["model"]["hidden_size"]
    assert cfg.norm_eps == doc["model"]["rms_norm_eps"]


@pytest.mark.parametrize("name", CONFIGS)
def test_a_key_neither_compared_nor_listed_fails_and_is_named(name):
    doc = copy.deepcopy(_doc(name))
    doc["model"]["some_published_key"] = 72
    with pytest.raises(ValueError, match="some_published_key"):
        model_config(doc)
    doc.setdefault("not_compared", {})["some_published_key"] = "a reason"
    model_config(doc)


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 1024), ("intermediate_size", 1), ("num_key_value_heads", 1),
    ("vocab_size", 7), ("tie_word_embeddings", False), ("hidden_act", "gelu"),
])
def test_a_compared_key_that_differs_fails(key, value):
    doc = copy.deepcopy(_doc("qwen2-1.5b"))
    doc["model"][key] = value
    with pytest.raises(ValueError, match=key):
        model_config(doc)


def test_a_file_maps_its_own_keys_to_program_fields():
    doc = copy.deepcopy(_doc("qwen2-1.5b"))
    doc["model"]["num_local_experts"] = 72
    doc["compared"] = {"num_local_experts": "moe.n_experts"}
    with pytest.raises(ValueError, match="num_local_experts"):
        model_config(doc)  # the dense preset has no experts
    del doc["model"]["num_local_experts"]
    doc["model"]["head_dim"] = 128
    doc["compared"] = {"head_dim": "resolved_head_dim"}
    assert model_config(doc).resolved_head_dim == 128
    doc["model"]["head_dim"] = 64
    with pytest.raises(ValueError, match="head_dim"):
        model_config(doc)


def test_the_catalog_loads_the_reference_a_config_names(tmp_path):
    bench = tiny.write(tmp_path)
    shutil.copy(os.path.join(HERE, "shifted_reference.py"), bench / "reference" / "shifted.py")
    cat = Catalog(tmp_path, bench)
    default = cat.reference({})
    assert default.__file__ == str(bench / "reference" / "model.py")
    assert cat.reference({"reference": "model"}) is default
    shifted = cat.reference({"reference": "shifted"})
    assert shifted.__file__ == str(bench / "reference" / "shifted.py")
    assert cat.reference({"reference": "shifted"}) is shifted
    for name in ("Spec", "FP8", "logits_at", "row_loss_and_grad"):
        assert hasattr(default, name) and hasattr(shifted, name)
    with pytest.raises(KeyError, match="missing"):
        cat.reference({"reference": "missing"})


@pytest.mark.parametrize("workload,config,check", [
    ("tiny-serve.chat", "tiny-serve", "mean_gap"),
    ("tiny-train.train", "tiny-train", "loss_gap"),
])
def test_the_checks_run_the_reference_the_config_names(tmp_path, workload, config, check):
    """With the shifted reference named, a sound run of either entry reads
    what that module computes: the served tokens far below its reversed
    best logit, the losses one above the program's."""
    bench = tiny.write(tmp_path)
    shutil.copy(os.path.join(HERE, "shifted_reference.py"), bench / "reference" / "shifted.py")
    path = bench / "configs" / f"{config}.json"
    doc = json.loads(path.read_text())
    path.write_text(json.dumps(dict(doc, reference="shifted")))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_cell.py"), str(tmp_path), workload,
         str(2**31 + 21), "2"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert not result["correct"]
    value = result["checks"][check]["value"]
    assert (value > 1.0) if check == "mean_gap" else value == pytest.approx(1.0, abs=1e-3)
