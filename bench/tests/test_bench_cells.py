"""Every cell of ``BENCHMARK.json`` runs end to end on the CPU at a tiny
size, past the look for a chip: sound runs come out correct, and the
control and each fault a cell can have come out not correct
(``cpu_cell.py``).  The cells and their cuts come from ``tiny.py``; a cell
without a cut fails here, naming itself.

Faults: serving, a token altered where it is produced; training, a step
that returns its state unchanged, and half of each batch left out.  The
exchange between chips does not exist in these one-chip cells.  The
tiny cells' limits (``tiny.py``) sit between their own readings: sound
runs read 0 to 1e-6, the controls 0.08 and more (serving) and 0.3 in the
change of the parameters (training), the faults 0.1 to 3.7."""

import json
import os
import subprocess
import sys

import pytest

from bench.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny")
    tiny.write(path)
    return path


def _run(root, workload, fault=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_cell.py"), str(root), workload,
         str(2**31 + 21), "2", fault],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


CELLS = tiny.cells()
REAL = json.loads((tiny.BENCH.parent / "BENCHMARK.json").read_text())["workloads"]


@pytest.mark.parametrize("index", range(len(REAL)), ids=[w["name"] for w in REAL])
def test_every_cell_has_a_tiny_cut(index):
    """Each real cell maps to a tiny one of the same mix, whose configuration
    and mix have cuts."""
    assert len(CELLS) == len(REAL)
    tiny_name, entry = CELLS[index]
    assert entry in tiny.FAULTS and tiny_name.endswith("." + REAL[index]["traffic"])


def test_a_cell_without_a_tiny_cut_is_named():
    with pytest.raises(KeyError, match="cell m.unknown has no tiny cut: add bench/tests/tiny_cuts/traffic/unknown.json"):
        tiny.cut("traffic", "unknown", "m.unknown")


@pytest.mark.parametrize("workload", [name for name, _ in CELLS])
def test_sound_run_is_correct(root, workload):
    result = _run(root, workload)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2


@pytest.mark.parametrize("workload,fault", [
    (name, fault) for name, entry in CELLS for fault in tiny.FAULTS[entry]])
def test_control_and_faults_are_not_correct(root, workload, fault):
    result = _run(root, workload, fault)
    assert not result["correct"], result["checks"]
