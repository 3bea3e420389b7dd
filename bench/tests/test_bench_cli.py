"""``bench/run.py`` gives no result without a TPU, or without the program."""

import os
import shutil
import subprocess
import sys

from bench.catalog import BENCH_DIR, ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-1.5b.chat",
         "--seed", str(2**31 + 7), "--seconds", "10", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_no_tpu_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
    assert "no TPU found" in proc.stderr


def test_benchmark_alone_gives_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert _no_result(proc.stdout)


def test_the_compile_cache_keeps_every_program(tmp_path):
    """A cache size limit in the environment does not reach the benchmark's
    cache: a limit would evict programs that the next run reads back."""
    code = ("import jax; from bench import harness; harness.configure_jax_cache(); "
            "print(jax.config.jax_compilation_cache_max_size, jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_MAX_SIZE="200000000",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    size, path = proc.stdout.split()
    assert size == "-1" and path == str(ROOT / ".jax_cache")
