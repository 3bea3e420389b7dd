"""Discovery by name, and the benchmark's definition against its
contract."""

import json
import re

from bench import harness
from bench.catalog import BENCH_DIR, ROOT, Catalog

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (bench / sub).mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "toy.mix", "config": "toy", "traffic": "mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "toy_tok_s", "unit": "tokens/s", "workloads": ["toy.mix"]}],
        "per_layer": [{"name": "toy_share", "unit": "%", "moves": "toy_tok_s"},
                      {"name": "other", "unit": "%", "moves": "other_tok_s"}],
    }))
    (bench / "configs" / "toy.json").write_text('{"system": {"entry": "serve"}}')
    (bench / "traffic" / "mix.json").write_text('{"kind": "open_loop"}')
    (bench / "limits" / "toy.mix.json").write_text('{"max_gap": 0.5}')
    (bench / "metrics" / "toy_share.py").write_text("def read(run):\n    return 42.0\n")
    cat = Catalog(tmp_path, bench)
    cell = cat.workload("toy.mix")
    assert cat.config(cell["config"])["system"]["entry"] == "serve"
    assert cat.traffic(cell["traffic"])["kind"] == "open_loop"
    assert cat.limits("toy.mix") == {"max_gap": 0.5}
    assert [m["name"] for m in cat.metrics("toy.mix", per_layer=False)] == ["setup_s", "toy_tok_s"]
    per_layer = cat.metrics("toy.mix", per_layer=True)
    assert [m["name"] for m in per_layer] == ["toy_share"]
    run = harness.Run(workload=cell, config={}, traffic={}, seed=1, seconds=1, peak={})
    assert harness.measure(run, cat, per_layer=True) == {"toy_share": {"value": 42.0, "unit": "%"}}


def test_every_name_in_the_benchmark_has_its_files():
    cat = Catalog()
    doc = cat.doc
    for c in doc["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for w in doc["workloads"]:
        cat.config(w["config"]), cat.traffic(w["traffic"]), cat.limits(w["name"])
        for per_layer in (False, True):
            for m in cat.metrics(w["name"], per_layer):
                assert callable(cat.reader(m["name"]))


def test_the_benchmark_keeps_its_contract():
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(doc) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert doc["paths"] == ["bench"] and BENCH_DIR.name == "bench"
    assert 1 <= doc["run_seconds"] <= 51
    cells = {w["name"]: w for w in doc["workloads"]}
    configs = {c["name"] for c in doc["configs"]}
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for w in doc["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    assert {w["config"] for w in doc["workloads"]} == configs
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    for w in cells:  # every cell: set-up, one other end-to-end, one per-layer
        cat = Catalog()
        assert len(cat.metrics(w, per_layer=False)) >= 2 and cat.metrics(w, per_layer=True)
