"""Model FLOP/s utilization of the engine's steps, in %: forward
operations of every prompt token prefilled and every token decoded by the
engine steps that ended in the window (``work.fwd_flops_per_token`` at
the model's own widths), over the summed host time of those steps and the
chip's peak.  Time in which the engine had no work is not counted, so a
faster step shows here at any offered load."""

from bench import work


def read(run):
    if "steps" not in run.data or "max_slots" not in run.data:
        return None
    steps = [s for s in run.data["steps"] if s["t1"] <= run.data["t_end"]]
    seconds = sum(s["t1"] - s["t0"] for s in steps)
    if not seconds:
        return None
    tokens = sum(s["prefill_tokens"] + s["decode_tokens"] for s in steps)
    w = work.Widths.of(run.data["cfg"])
    return 100.0 * tokens * work.fwd_flops_per_token(w) / seconds / run.peak["bf16_flops_per_s"]
