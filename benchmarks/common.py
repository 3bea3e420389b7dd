"""Benchmark helpers: timing + CSV emission."""

from __future__ import annotations

import time

import jax


def time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall time in microseconds (post-jit, blocked until ready)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    return times[len(times) // 2]


def refuse_on_tpu(name: str) -> None:
    """Exit non-zero when this process runs on a TPU.

    The caller measures emulated CPU devices in a child process; on a TPU
    host its rows would be CPU numbers under this host's name.
    """
    if jax.default_backend() == "tpu":
        raise SystemExit(
            f"{name}: measures 4 emulated CPU devices in a child process, "
            "so it cannot measure this TPU; refusing to report CPU numbers"
        )


def emit(name: str, us: float, derived: str) -> str:
    row = f"{name},{us:.1f},{derived}"
    print(row)
    return row
