"""Model FLOP/s utilization of training, in %: required operations per
step (``work.train_step_flops``: 6·N per token plus three times the Taylor
forward; recomputation does not count) times steps per second, over the
chip's peak.  The seconds in which the profiler started and stopped, and
the host dispatched nothing, are not counted."""

from bench import work


def read(run):
    if "batch" not in run.data:
        return None
    w = work.Widths.of(run.data["cfg"])
    flops = work.train_step_flops(w, run.data["batch"], run.data["seq"]) * run.data["steps"]
    seconds = run.window_s - run.data.get("trace_pause_s", 0.0)
    return 100.0 * flops / seconds / run.peak["bf16_flops_per_s"]
