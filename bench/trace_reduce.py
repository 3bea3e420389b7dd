"""Profiler trace to numbers: device busy and idle time, device time per
program and per operation, and what the host was doing in each idle gap.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX (``jax.profiler.ProfileData``).  The traced span is the host span
named ``WINDOW`` that the harness opens around the traced work; device
events are clipped to it.

* Device planes are named ``/device:TPU:<i>``.  Operations are the events
  of their ``XLA Ops`` lines, programs those of ``XLA Modules``.
* Busy time is the union of the operation intervals; idle is the rest of
  the span.  With several chips both are averaged over the chips.
* Time per operation leaves out the control-flow operations (``while``,
  ``conditional``) whose events span the operations they run.
* Host spans are the events of the host plane (``/host:CPU``) whose names
  start with ``bench.``: the harness's own ``TraceAnnotation`` spans.  An
  idle gap is put down to the innermost such span that covers its middle.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
CONTAINERS = (" while(", " conditional(")
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Summary:
    """What one trace says, in seconds."""

    window_s: float
    busy_s: float                      # averaged over the chips
    chips: int
    op_s: Dict[str, float]             # device time per operation name
    op_count: Dict[str, int]
    module_s: Dict[str, float]         # device time per program name
    module_count: Dict[str, int]
    idle_by_host_span: Dict[str, float]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.idle_by_host_span.items(), key=lambda kv: -kv[1])[:n]

    def time_of(self, names, table: str = "op", also: str = "") -> Tuple[float, int]:
        """Summed time and event count of the operations (or programs,
        ``table="module"``) whose names contain any of ``names`` and
        ``also``.  On a TPU an operation's name is its HLO instruction."""
        secs = self.op_s if table == "op" else self.module_s
        count = self.op_count if table == "op" else self.module_count
        hits = [k for k in secs if also in k and any(n in k for n in names)]
        return sum(secs[k] for k in hits), sum(count[k] for k in hits)


def find_xplane(trace_dir) -> pathlib.Path:
    paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _gaps(busy: List[Tuple[int, int]], t0: int, t1: int):
    cur = t0
    for s, e in busy:
        if s > cur:
            yield cur, s
        cur = max(cur, e)
    if t1 > cur:
        yield cur, t1


def reduce_profile(pd) -> Summary:
    """A ``Summary`` of a ``jax.profiler.ProfileData``."""
    host_spans: List[Tuple[int, int, str]] = []
    window: Optional[Tuple[int, int]] = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("bench."):
                        continue
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if ev.name == WINDOW:
                        window = (s, e)
                    else:
                        host_spans.append((s, e, ev.name))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    if not devices:
        raise ValueError("the trace holds no device plane")
    t0, t1 = window
    op_s: Dict[str, float] = collections.defaultdict(float)
    op_n: Dict[str, int] = collections.Counter()
    mod_s: Dict[str, float] = collections.defaultdict(float)
    mod_n: Dict[str, int] = collections.Counter()
    idle: Dict[str, float] = collections.defaultdict(float)
    busy_total = 0.0
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s = max(int(ev.start_ns), t0)
                e = min(int(ev.start_ns) + int(ev.duration_ns), t1)
                if e <= s:
                    continue
                if line.name == OPS_LINE:
                    ops.append((s, e))
                    if not any(c in ev.name for c in CONTAINERS):
                        op_s[ev.name] += (e - s) / 1e9
                        op_n[ev.name] += 1
                else:
                    mod_s[ev.name] += (e - s) / 1e9
                    mod_n[ev.name] += 1
        busy = _union(ops)
        busy_total += sum(e - s for s, e in busy) / 1e9
        for gs, ge in _gaps(busy, t0, t1):
            mid = (gs + ge) // 2
            covering = [h for h in host_spans if h[0] <= mid < h[1]]
            name = min(covering, key=lambda h: h[1] - h[0])[2] if covering else "bench.none"
            idle[name] += (ge - gs) / 1e9 / len(devices)
    return Summary(
        window_s=(t1 - t0) / 1e9, busy_s=busy_total / len(devices),
        chips=len(devices), op_s=dict(op_s), op_count=dict(op_n),
        module_s=dict(mod_s), module_count=dict(mod_n),
        idle_by_host_span=dict(idle),
    )


def reduce_dir(trace_dir) -> Summary:
    from jax.profiler import ProfileData  # noqa: PLC0415

    return reduce_profile(ProfileData.from_file(str(find_xplane(trace_dir))))
