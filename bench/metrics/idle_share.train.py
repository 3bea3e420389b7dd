"""Share of the traced span in which no operation ran on the device, in
%: one minus the union of the device's operation intervals over the span
(``trace_reduce``)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
