"""Host time of one decode step, in ms: engine steps in the window that
made exactly one decode dispatch and no prefill, their time over the
dispatch's scan length; the median of these."""

import statistics


def read(run):
    per = [1e3 * (s["t1"] - s["t0"]) / s["calls"][0][0]
           for s in run.data.get("steps", ())
           if s["t1"] <= run.data["t_end"] and s["decode_dispatches"] == 1
           and not s["prefill_dispatches"] and len(s["calls"]) == 1]
    return statistics.median(per) if per else None
