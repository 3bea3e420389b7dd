"""Share of the engine's slots occupied after each step that dispatched a
decode block in the window, averaged, in %."""


def read(run):
    steps = [s for s in run.data.get("steps", ())
             if s["decode_dispatches"] and s["t1"] <= run.data["t_end"]]
    if not steps:
        return None
    return 100.0 * sum(s["occupied"] for s in steps) / (len(steps) * run.data["max_slots"])
