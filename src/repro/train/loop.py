"""Fault-tolerant training loop.

Single-controller model: this process is re-launched by the cluster
scheduler after any failure; the loop resumes from the newest *committed*
checkpoint (torn saves are invisible by construction).  The data pipeline is
stateless in the step index, so resume is sample-exact.  Checkpoints are
written asynchronously (bounded lost work, no step stall) every
``checkpoint_every`` steps and on exit.

``max_wall_seconds`` simulates preemption in tests: the loop exits cleanly
mid-run and a second invocation must continue to the target step.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint, wait_for_saves
from repro.train.step import TrainState


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 100
    log_every: int = 10
    keep: int = 3
    async_save: bool = True
    max_wall_seconds: Optional[float] = None


def run_training(
    step_fn: Callable,
    state: TrainState,
    batch_at: Callable[[int], Dict[str, np.ndarray]],
    loop: TrainLoopConfig,
    state_shardings=None,
    log: Callable[[str], None] = print,
) -> TrainState:
    start_step = 0
    if loop.checkpoint_dir and latest_step(loop.checkpoint_dir) is not None:
        ck = latest_step(loop.checkpoint_dir)
        state = restore_checkpoint(
            loop.checkpoint_dir, state, step=ck, shardings=state_shardings
        )
        start_step = int(jax.device_get(state.step))
        log(f"[loop] resumed from checkpoint step {start_step}")

    t0 = time.monotonic()
    for step in range(start_step, loop.total_steps):
        state, metrics = step_fn(state, batch_at(step))
        if loop.log_every and (step + 1) % loop.log_every == 0:
            m = {k: float(jax.device_get(v)) for k, v in metrics.items()}
            log(f"[loop] step {step + 1}/{loop.total_steps} " +
                " ".join(f"{k}={v:.4f}" for k, v in sorted(m.items())))
        if (
            loop.checkpoint_dir
            and loop.checkpoint_every
            and (step + 1) % loop.checkpoint_every == 0
        ):
            save_checkpoint(
                loop.checkpoint_dir, step + 1, state,
                block=not loop.async_save, keep=loop.keep,
            )
        if loop.max_wall_seconds and time.monotonic() - t0 > loop.max_wall_seconds:
            log(f"[loop] wall-clock budget hit at step {step + 1} (simulated preemption)")
            break

    if loop.checkpoint_dir:
        final = int(jax.device_get(state.step))
        if latest_step(loop.checkpoint_dir) != final:
            save_checkpoint(loop.checkpoint_dir, final, state, block=True, keep=loop.keep)
        wait_for_saves()
    return state
