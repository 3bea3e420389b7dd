"""The reduction from a profiler trace to busy and idle time, time per
operation and per program, and idle gaps put down to host spans: on a
small made-up trace with known answers, and on a small trace recorded on
a TPU v5e chip (``data/``)."""

import pathlib

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce

DATA = pathlib.Path(__file__).with_name("data")

# One device plane with two overlapping operations and one program, a host
# plane with the window and two spans of the harness (times in ns; offsets
# and durations in ps).
TEXT = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "_taylor_fwd_kernel" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.serve.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.check" } }
  event_metadata { key: 4 value { id: 4 name: "python_function" } } }
"""


def test_made_up_trace():
    s = trace_reduce.reduce_profile(ProfileData.from_text_proto(TEXT))
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(3e-6)          # [1000, 4000] ns; the third op is past the window
    assert s.idle_share == pytest.approx(0.7)
    assert s.op_s == pytest.approx({"fusion.1": 2e-6, "_taylor_fwd_kernel": 2e-6})
    assert s.time_of(["_taylor_fwd"]) == (pytest.approx(2e-6), 1)
    assert s.time_of(["jit_step"], table="module") == (pytest.approx(3e-6), 1)
    # [0, 1000] falls in the step span, [4000, 10000] in the check's
    assert s.idle_by_host_span == pytest.approx({"bench.serve.step": 1e-6, "bench.check": 6e-6})
    assert s.top_idle(1) == [("bench.check", pytest.approx(6e-6))]


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_profile(ProfileData.from_text_proto(TEXT.replace("bench.window", "x")))


def test_trace_recorded_on_a_v5e():
    # Three 2048² bf16 matmuls (10 ms apart) and one Pallas forward kernel
    # of Taylor attention (9 / 3 heads of 64, 512 tokens), each in a span,
    # inside the window.  The device clock runs about 1 ms ahead of the
    # host's, so the first matmul, dispatched just after the window opened,
    # lies before it on the device and is left out.
    s = trace_reduce.reduce_dir(DATA)
    assert s.chips == 1
    assert s.window_s == pytest.approx(0.034347407)
    assert s.busy_s == pytest.approx(0.000511368)
    assert s.idle_share == pytest.approx(1 - 0.000511368 / 0.034347407)
    assert s.time_of(["jit__lambda"], table="module") == (pytest.approx(0.000180263), 2)
    assert s.time_of(["jit_taylor_attention_kernel"], table="module") == (
        pytest.approx(0.000331311), 1)
    pallas = 'custom_call_target="tpu_custom_call"'
    assert s.time_of(["%taylor_attention_kernel"], also=pallas) == (pytest.approx(0.000321681), 1)
    assert s.time_of([pallas]) == s.time_of(["%taylor_attention_kernel"], also=pallas)
    assert s.idle_by_host_span == pytest.approx(
        {"bench.none": 0.03192869, "bench.kernel": 0.001907349})
