"""Both cells run end to end on the CPU at a tiny size, past the look for a
chip: sound runs come out correct, and the control and each fault a cell
can have come out not correct (``cpu_cell.py``).

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


@pytest.mark.parametrize("workload", ["tiny-serve.chat", "tiny-train.train"])
def test_sound_run_is_correct(root, workload):
    result = _run(root, workload)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2


@pytest.mark.parametrize("workload,fault", [
    ("tiny-serve.chat", "token"), ("tiny-serve.chat", "control"),
    ("tiny-train.train", "unchanged"), ("tiny-train.train", "half"),
    ("tiny-train.train", "control"),
])
def test_control_and_faults_are_not_correct(root, workload, fault):
    result = _run(root, workload, fault)
    assert not result["correct"], result["checks"]
