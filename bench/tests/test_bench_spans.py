"""The engine's phases on the device trace's clock (``bench/phases.py``):
the host–device clock offset, idle gaps put down to ``serve.*`` spans, the
host stall, and the readings of ``ServeEngine.timings()`` per step — on
made-up traces with known answers and on the trace recorded on a v5e."""

import math
import pathlib

import pytest
from jax.profiler import ProfileData

from bench import phases

DATA = pathlib.Path(__file__).with_name("data")
MS = 1_000_000     # ns

# Two engine steps inside the window, on the host's clock (ms): the first
# admits (a prefill) and decodes, then sweeps; the second only decodes.
HOST = [
    ("bench.window", 0, 100),
    ("bench.serve.step", 0.5, 45), ("serve.step", 1, 44),
    ("serve.admit", 2, 12), ("serve.prefill", 2.5, 9),
    ("serve.decode", 12.5, 40), ("serve.health", 40.5, 43),
    ("bench.serve.step", 50, 95), ("serve.step", 50.5, 94),
    ("serve.decode", 60, 90),
]
# Device work on the host's clock: the prefill, two decode blocks (one
# program event each), the health sweep.
OPS = [(3, 8.5), (13.5, 39), (41, 42), (61, 89)]
DECODES = [(13.5, 39), (61, 89)]
AHEAD = 1.0        # the device clock runs 1 ms ahead of the host's


def _events(items, names):
    out = []
    for name, s, e in items:
        out.append(f"events {{ metadata_id: {names.index(name) + 1} "
                   f"offset_ps: {round(s * MS * 1000)} duration_ps: {round((e - s) * MS * 1000)} }}")
    return "\n".join(out)


def _meta(names):
    return "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                     for i, n in enumerate(names, 1))


def trace(ahead=AHEAD, decodes=DECODES, host=HOST):
    ops = [("fusion", s + ahead, e + ahead) for s, e in OPS]
    mods = [("jit_scan_fn(123)", s + ahead, e + ahead) for s, e in decodes]
    dev_names = ["fusion", "jit_scan_fn(123)"]
    host_names = sorted({n for n, _, _ in host})
    return ProfileData.from_text_proto(f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {_events(ops, dev_names)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {_events(mods, dev_names)} }}
  {_meta(dev_names)} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {_events(host, host_names)} }}
  {_meta(host_names)} }}
""")


def test_offset_of_a_device_clock_1ms_ahead():
    off = phases.clock_offset(trace())
    # decode 1: o in [12.5 - 14.5, 40 - 40]; decode 2: [60 - 62, 90 - 90]
    assert off.pairs == 2 and off.note == ""
    assert off.width_s == pytest.approx(2e-3)
    assert abs(off.seconds - (-AHEAD * 1e-3)) <= off.width_s
    assert off.seconds == pytest.approx(-1e-3)


def test_offset_skips_programs_of_spans_cut_by_the_window():
    # a decode that began before the window opened: its span is not wholly
    # inside, its program event is on the device; the later run fits
    host = HOST + [("serve.decode", -30, 0.2)]
    off = phases.clock_offset(trace(decodes=[(-29, 0)] + DECODES, host=host))
    assert off.pairs == 2 and off.seconds == pytest.approx(-1e-3)


def test_an_empty_intersection_falls_back_to_0_and_says_so():
    # the second block's program event 5 ms later than its span allows
    late = [DECODES[0], (DECODES[1][0] + 5, DECODES[1][1] + 5)]
    off = phases.clock_offset(trace(decodes=late))
    assert off.seconds == 0.0 and math.isnan(off.width_s)
    assert "empty intersection" in off.note


def test_no_pairs_falls_back_to_0_and_says_so():
    off = phases.clock_offset(trace(), span="serve.nothing")
    assert off.seconds == 0.0 and off.pairs == 0 and "no pairs" in off.note


def test_a_gap_inside_admission_is_put_down_to_it():
    a = phases.align(trace())
    assert a.offset.seconds == pytest.approx(-1e-3)
    # gaps (host clock, ms): [0, 3] [8.5, 13.5] [39, 41] [42, 61] [89, 100]
    assert a.idle_by_span == pytest.approx({
        "serve.step": 1e-3 * (3 + 2 + 19),     # middles 1.5, 40, 51.5
        "serve.admit": 5e-3,                   # middle 11, in [2, 12)
        "bench.serve.step": 11e-3,             # middle 94.5
    })
    assert a.idle_s == pytest.approx(sum(a.idle_by_span.values()))
    assert a.top_idle(1) == [("serve.step", pytest.approx(24e-3))]
    # unaligned, the admission's gap (middle 12 ms) falls just past it and
    # the first gap ([0, 4], middle 2 ms) into it; the last gap (middle
    # 95 ms) falls past every span
    b = phases.align(trace(), phases.Offset(0.0, math.nan, 0))
    assert b.idle_by_span["serve.admit"] == pytest.approx(4e-3)
    assert b.idle_by_span["bench.none"] == pytest.approx(10e-3)
    assert "bench.none" not in a.idle_by_span


def test_host_stall_is_idle_in_steps_outside_their_decodes():
    a = phases.align(trace())
    # step minus decode: [1, 12.5] [40, 44] [50.5, 60] [90, 94]; idle in
    # them: 2 + 4 + (1 + 2) + 9.5 + 4 ms
    assert a.idle_within("serve.step", ("serve.decode",)) == pytest.approx(22.5e-3)
    assert phases.host_stall_share(a) == pytest.approx(22.5)


def test_host_stall_is_none_without_engine_spans():
    host = [h for h in HOST if not h[0].startswith("serve.")]
    assert phases.host_stall_share(phases.align(trace(host=host))) is None


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        phases.align(trace(host=HOST[1:]))


def test_offset_on_the_trace_recorded_on_a_v5e():
    # Three 2048² matmuls, each in a bench.step span that waits for it: the
    # device clock runs 1.13–1.70 ms behind the host's.
    pd = ProfileData.from_file(str(next(DATA.glob("*.xplane.pb"))))
    off = phases.clock_offset(pd, span="bench.step", program="jit__lambda")
    assert off.pairs == 3
    assert off.seconds == pytest.approx((1_126_569 + 1_695_402) / 2e9)
    assert off.width_s == pytest.approx(568_833e-9)
    a = phases.align(pd, off)
    # aligned, the first matmul lies inside the window and its step
    assert a.idle_s == pytest.approx(a.window_s - 0.000511368 - 0.000101526, abs=2e-6)


def _step(t1, prefill=0, chunk=0, decode=0, step=100, calls=((16, 6),), n=1):
    return dict(t1=t1, calls=list(calls),
                timings=dict(step_ns=step, prefill_ns=prefill, prefill_chunk_ns=chunk,
                             decode_ns=decode, decode_n=n))


def test_prefill_share_reads_steps_in_the_window():
    steps = [_step(1, prefill=20), _step(2, chunk=10), _step(3, decode=70), _step(9, prefill=100)]
    assert phases.prefill_share(steps, t_end=5) == pytest.approx(10.0)
    assert phases.prefill_share([dict(t1=1, calls=[])], t_end=5) is None
    assert phases.prefill_share([], t_end=5) is None


def test_decode_dispatch_ms_is_the_median_per_scan_step():
    steps = [_step(1, decode=32e6), _step(2, decode=64e6, calls=((16, 6),)),
             _step(3, decode=80e6), _step(4, decode=8e6, calls=((4, 1),)),
             _step(5, decode=0, calls=(), n=0), _step(99, decode=1e9)]
    # per scan step: 2, 4, 5, 2 ms; the step without a decode is left out
    assert phases.decode_dispatch_ms(steps, t_end=10) == pytest.approx(3.0)
    assert phases.decode_dispatch_ms([dict(t1=1, calls=[(16, 6)])], t_end=10) is None
