"""Engine observability: ``ServeEngine.timings()`` phase counters, the
``serve.*`` trace annotations, and the per-request ``admitted_at`` — and
that none of it touches ``stats()``, which replays compare."""

import pathlib

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import lm_init
from repro.serve import (
    Request,
    RequestRejected,
    ResiliencePolicy,
    SchedulerPolicy,
    ServeEngine,
    Status,
)
from repro.serve.scheduler import PHASES

CHUNK = 8
LONG = 20          # prompt tokens: three chunks of CHUNK
HEALTH_EVERY = 2


@pytest.fixture(scope="module")
def served():
    cfg = get_reduced("smollm-135m")
    return cfg, lm_init(jax.random.PRNGKey(0), cfg)


def _engine(served, **kw):
    cfg, params = served
    kw.setdefault("max_slots", 2)
    kw.setdefault("n_max", 64)
    kw.setdefault("decode_block", 4)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("policy", ResiliencePolicy(health_check_every=HEALTH_EVERY))
    return ServeEngine(params, cfg, **kw)


def _mixed_run(served, clock=None):
    """Two equal-length prompts (one batched prefill), one of another
    length (a second group), one long prompt (chunked).  Steps to the end;
    returns the engine, its step count and the results."""
    eng = _engine(served, clock=clock)
    rng = np.random.default_rng(3)
    prompt = lambda n: rng.integers(0, served[0].vocab, n).astype(np.int32)
    for n, new in ((6, 5), (6, 3), (5, 4), (LONG, 6)):
        eng.submit(Request(tokens=prompt(n), max_new_tokens=new))
    steps = 0
    while eng.step():
        steps += 1
    return eng, steps + 1, eng.poll()


@pytest.fixture(scope="module")
def mixed(served):
    return _mixed_run(served)


def test_timings_count_each_phase_as_often_as_it_ran(mixed):
    eng, steps, results = mixed
    t, st = eng.timings(), eng.stats()
    assert set(t) == {f"{p}_{u}" for p in PHASES for u in ("ns", "n")}
    assert all(results[r].status is Status.OK for r in results)
    assert t["step_n"] == steps == st["blocks"]
    assert t["admit_n"] == steps              # admission runs every step
    assert t["prefill_n"] == 2                # one per batched group
    assert t["prefill_chunk_n"] == -(-LONG // CHUNK)
    assert t["prefill_n"] + t["prefill_chunk_n"] == st["prefill_dispatches"]
    assert t["decode_n"] == st["decode_dispatches"]
    # the sweep runs every HEALTH_EVERY-th block that holds a request
    assert t["health_n"] == st["health_checks"]
    assert 0 < t["health_n"] <= steps // HEALTH_EVERY
    assert all(t[f"{p}_ns"] > 0 for p in PHASES)


def test_step_time_holds_its_children(mixed):
    t = mixed[0].timings()
    assert t["step_ns"] >= t["admit_ns"] + t["decode_ns"] + t["health_ns"]
    assert t["admit_ns"] >= t["prefill_ns"] + t["prefill_chunk_ns"]


def test_health_phase_follows_health_check_every(served):
    cfg = served[0]
    for every in (0, 1, 3):
        eng = _engine(served, policy=ResiliencePolicy(health_check_every=every))
        eng.submit(Request(tokens=np.arange(5, dtype=np.int32) % cfg.vocab,
                           max_new_tokens=24))
        eng.run()
        blocks = eng.stats()["blocks"]
        # the request holds its slot in every block, so each
        # every-th block sweeps
        assert eng.timings()["health_n"] == (blocks // every if every else 0)
        assert eng.timings()["health_n"] == eng.stats().get("health_checks", 0)


def test_stats_unchanged_by_the_instrumentation(served, mixed):
    clock = iter(range(10**6)).__next__          # a virtual clock
    a, _, _ = _mixed_run(served, clock=clock)
    clock = iter(range(10**6)).__next__
    b, _, _ = _mixed_run(served, clock=clock)
    assert a.stats() == b.stats()
    assert not set(a.stats()) & set(a.timings())
    assert not any(k.endswith("_ns") for k in a.stats())
    counts = lambda eng: {k: v for k, v in eng.timings().items() if k.endswith("_n")}
    assert counts(a) == counts(b) == counts(mixed[0])


def _preempted_run(served):
    eng = _engine(served, max_slots=1, sched=SchedulerPolicy(preemption=True),
                  clock=iter(range(10**6)).__next__)
    rng = np.random.default_rng(1)
    lo = eng.submit(Request(tokens=rng.integers(0, 50, 6).astype(np.int32),
                            max_new_tokens=10, priority=5))
    for _ in range(2):
        eng.step()
    hi = eng.submit(Request(tokens=rng.integers(0, 50, 8).astype(np.int32),
                            max_new_tokens=6, priority=0))
    res = eng.run(return_results=True)
    assert res[lo].preemptions >= 1 and eng.stats()["resumes"] >= 1
    return [res[lo], res[hi]]


@pytest.mark.parametrize("case", ["mixed", "preempted"])
def test_request_lifecycle_is_ordered(served, mixed, case):
    results = list(mixed[2].values()) if case == "mixed" else _preempted_run(served)
    for r in results:
        assert r.status is Status.OK
        assert r.submitted_at <= r.admitted_at <= r.first_token_at <= r.finished_at


def test_a_rejected_request_was_never_admitted(served):
    eng = _engine(served)
    with pytest.raises(RequestRejected) as e:
        eng.submit(Request(tokens=np.zeros(0, np.int32), max_new_tokens=1))
    res = eng.poll()[e.value.rid]
    assert res.status is Status.REJECTED and res.admitted_at is None


def test_serve_spans_in_a_cpu_trace(served, tmp_path):
    from jax.profiler import ProfileData  # noqa: PLC0415

    jax.profiler.start_trace(str(tmp_path))
    try:
        eng, _, _ = _mixed_run(served)
    finally:
        jax.profiler.stop_trace()
    pb = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    spans = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))
    t = eng.timings()
    assert {f"serve.{p}": t[f"{p}_n"] for p in PHASES} == {
        k: len(v) for k, v in spans.items()}
    # every decode lies inside a step
    for s, e, _ in spans["serve.decode"]:
        assert any(ss <= s and e <= se for ss, se, _ in spans["serve.step"])
    assert sorted((a["group"], a["length"]) for *_, a in spans["serve.prefill"]) == [(1, 5), (2, 6)]
