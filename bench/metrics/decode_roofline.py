"""Share of its roofline that the decode scan reaches, in %, from the
trace.  Required bytes per scan step: the bf16 weights once and each
active slot's dense fp32 moment state read once and written once
(``work.decode_step_bytes``), summed over the decode dispatches of the
steps wholly inside the trace, over the device time of the decode-scan
program in the trace.  Bound by bytes."""

from bench import work

PROGRAM = "jit_scan_fn"  # the jitted decode scan (serve/engine.py: scan_fn)


def read(run):
    if run.trace is None or "steps" not in run.data:
        return None
    seconds, count = run.trace.time_of([PROGRAM], table="module")
    calls = [c for s in run.data["steps"] if s["traced"] for c in s["calls"]]
    if not seconds or count != len(calls):
        return None
    w = work.Widths.of(run.data["cfg"])
    nbytes = sum(steps * work.decode_step_bytes(w, active) for steps, active in calls)
    share, _ = work.roofline_share(0.0, nbytes, seconds, run.peak)
    return share
