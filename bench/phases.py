"""The serve engine's phases on the device trace's clock, and what its
``timings()`` say per step.

Reads a ``jax.profiler.ProfileData`` that holds the harness's ``bench.*``
spans and the engine's ``serve.*`` spans (``ServeEngine.timings()`` names
the phases; docs/serving.md §Observability):

* ``clock_offset``: the host clock minus the device clock.  The n-th
  ``serve.decode`` span wholly inside the window is paired with the n-th
  device event of the decode program (``jit_scan_fn``).  The host
  dispatched first and waited for the tokens, so every true offset ``o``
  has ``dev_start + o >= host_start`` and ``dev_end + o <= host_end``: it
  lies in the intersection of ``[host_start - dev_start, host_end -
  dev_end]`` over the pairs, whose midpoint and width are reported.  Where
  the device holds more events of the program than there are spans (ones
  that ran in spans cut by the window's edges), each run of consecutive
  events is tried; the one run whose intersection is not empty is used.
  With no such run, or no pairs, the offset is 0 and ``note`` says why.
* ``align``: device operations moved onto the host clock by that offset;
  each idle gap in the window is put down to the innermost ``bench.`` or
  ``serve.`` span that covers its middle (``bench.none`` where none does),
  and idle time inside some spans but outside others is summed exactly.

``bench/trace_reduce.py`` computes the harness's own numbers
(``idle_share`` and the unaligned ``idle_gaps``); this module adds to them
and changes none.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace_reduce import (
    DEVICE_PREFIX,
    HOST_PLANE,
    MODULES_LINE,
    OPS_LINE,
    WINDOW,
    _gaps,
    _union,
)

PREFIXES = ("bench.", "serve.")
DECODE_SPAN = "serve.decode"
DECODE_PROGRAM = "jit_scan_fn"   # serve/engine.py's jitted decode scan
NO_SPAN = "bench.none"

Interval = Tuple[int, int]


@dataclasses.dataclass
class Offset:
    """Host clock minus device clock, in seconds."""

    seconds: float
    width_s: float       # width of the interval it lies in; nan where none
    pairs: int
    note: str = ""       # why 0 was used, where it was


@dataclasses.dataclass
class Aligned:
    """Idle time of a trace on the host clock, put down to host spans."""

    offset: Offset
    window_s: float
    idle_s: float                       # averaged over the chips
    idle_by_span: Dict[str, float]
    spans: List[Tuple[int, int, str]]
    gaps: List[List[Interval]]          # per chip, host clock, ns

    def top_idle(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]

    def idle_within(self, inside: str, outside: Sequence[str] = ()) -> float:
        """Idle seconds (averaged over the chips) inside the spans named
        ``inside`` and inside none of the spans named in ``outside``."""
        ins = _union([(s, e) for s, e, n in self.spans if n == inside])
        outs = _union([(s, e) for s, e, n in self.spans if n in outside])
        total = 0
        for gaps in self.gaps:
            hit = _intersect(gaps, ins)
            total += _length(hit) - _length(_intersect(hit, outs))
        return total / 1e9 / max(len(self.gaps), 1)


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def host_spans(pd) -> Tuple[Interval, List[Tuple[int, int, str]]]:
    """The window and every other ``bench.`` / ``serve.`` span of the host
    plane, as ``(start_ns, end_ns, name)``."""
    window, spans = None, []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PREFIXES):
                    continue
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if ev.name == WINDOW:
                    window = (s, e)
                else:
                    spans.append((s, e, ev.name))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    return window, sorted(spans)


def _devices(pd) -> list:
    devices = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError("the trace holds no device plane")
    return sorted(devices, key=lambda p: p.name)


def _events(plane, line_name: str, contains: str = "") -> List[Interval]:
    return sorted((int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns))
                  for line in plane.lines if line.name == line_name
                  for ev in line.events if contains in ev.name)


def clock_offset(pd, span: str = DECODE_SPAN,
                 program: str = DECODE_PROGRAM) -> Offset:
    """Host minus device clock from the ``span`` spans wholly inside the
    window and the first chip's events of ``program`` (module docstring)."""
    (t0, t1), spans = host_spans(pd)
    host = [(s, e) for s, e, n in spans if n == span and t0 <= s and e <= t1]
    dev = _events(_devices(pd)[0], MODULES_LINE, program)
    if not host or len(dev) < len(host):
        return Offset(0.0, math.nan, 0,
                      f"no pairs: {len(host)} {span} spans, {len(dev)} {program} events")
    fits = []
    for k in range(len(dev) - len(host) + 1):
        pairs = list(zip(host, dev[k:]))
        lo = max(hs - ds for (hs, _), (ds, _) in pairs)
        hi = min(he - de for (_, he), (_, de) in pairs)
        if lo <= hi:
            fits.append((lo, hi))
    if len(fits) != 1:
        why = "empty intersection" if not fits else f"{len(fits)} runs of events fit"
        return Offset(0.0, math.nan, len(host), f"{why} over {len(host)} pairs")
    lo, hi = fits[0]
    return Offset((lo + hi) / 2e9, (hi - lo) / 1e9, len(host))


def align(pd, offset: Optional[Offset] = None) -> Aligned:
    """Idle gaps of the window on the host clock (the device's operations
    moved by ``offset``, by default ``clock_offset(pd)``), each put down to
    the innermost span covering its middle."""
    offset = clock_offset(pd) if offset is None else offset
    (t0, t1), spans = host_spans(pd)
    shift = round(offset.seconds * 1e9)
    devices = _devices(pd)
    per_chip, idle = [], {}
    for plane in devices:
        ops = [(max(s + shift, t0), min(e + shift, t1))
               for s, e in _events(plane, OPS_LINE)]
        gaps = list(_gaps(_union([(s, e) for s, e in ops if s < e]), t0, t1))
        per_chip.append(gaps)
        for gs, ge in gaps:
            mid = (gs + ge) // 2
            covering = [h for h in spans if h[0] <= mid < h[1]]
            name = min(covering, key=lambda h: h[1] - h[0])[2] if covering else NO_SPAN
            idle[name] = idle.get(name, 0.0) + (ge - gs) / 1e9 / len(devices)
    return Aligned(offset=offset, window_s=(t1 - t0) / 1e9,
                   idle_s=sum(_length(g) for g in per_chip) / 1e9 / len(devices),
                   idle_by_span=idle, spans=spans, gaps=per_chip)


# -- readings of a traced serving run ----------------------------------------


def host_stall_share(aligned: Aligned) -> Optional[float]:
    """Device idle time inside ``serve.step`` but inside none of its
    ``serve.decode`` children, as % of the window: the chip waiting for the
    engine's admission, bookkeeping and health sweep.  None where the
    trace holds no ``serve.step``."""
    if not any(n == "serve.step" for _, _, n in aligned.spans):
        return None
    return 100.0 * aligned.idle_within("serve.step", (DECODE_SPAN,)) / aligned.window_s


def prefill_share(steps: Sequence[dict], t_end: float) -> Optional[float]:
    """Share of the engine's step time spent in prefills, in %: over the
    steps that ended by ``t_end`` and carry ``timings`` (the step's
    ``ServeEngine.timings()`` delta), Σ(``prefill_ns`` +
    ``prefill_chunk_ns``) / Σ ``step_ns``.  None where no step carries
    timings."""
    timed = [s["timings"] for s in steps if s["t1"] <= t_end and "timings" in s]
    step_ns = sum(t["step_ns"] for t in timed)
    if not step_ns:
        return None
    return 100.0 * sum(t["prefill_ns"] + t["prefill_chunk_ns"] for t in timed) / step_ns


def decode_dispatch_ms(steps: Sequence[dict], t_end: float) -> Optional[float]:
    """Median over the steps that ended by ``t_end`` with exactly one
    decode dispatch of the ``serve.decode`` time over the dispatch's scan
    length (``calls``: ``(steps, active slots)`` per dispatch), in ms."""
    per = []
    for s in steps:
        t = s.get("timings")
        if s["t1"] <= t_end and t and t["decode_n"] == 1 and len(s["calls"]) == 1:
            per.append(t["decode_ns"] / 1e6 / s["calls"][0][0])
    return statistics.median(per) if per else None
