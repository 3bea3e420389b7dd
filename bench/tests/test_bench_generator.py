"""The seeded traffic generator."""

import json
import pathlib

import numpy as np
import pytest

from bench import generator

CHAT = json.loads((pathlib.Path(__file__).parents[1] / "traffic" / "chat.json").read_text())
SEED = 2**31 + 12345


def _mix(rate=2.0):
    return dict(CHAT, arrivals={"process": "poisson", "rate_per_s": rate})


def test_same_seed_same_requests():
    a = generator.make_requests(_mix(), SEED, 30, 151936)
    b = generator.make_requests(_mix(), SEED, 30, 151936)
    assert len(a) == len(b) == 60
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


def test_seeds_share_one_schedule_and_draw_their_own_tokens():
    """Every seed offers the same gaps and lengths; the seed draws their
    order and the token ids."""
    a = generator.make_requests(_mix(), SEED, 30, 151936)
    b = generator.make_requests(_mix(), SEED + 1, 30, 151936)
    gaps = lambda reqs: sorted(np.round(np.diff([r.due_s for r in reqs]), 9))
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert len(set(gaps(a)) & set(gaps(b))) >= len(a) - 3   # the last gap of each is cut
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_lengths_medians_clips_and_rounding():
    reqs = generator.make_requests(_mix(rate=20.0), SEED, 50, 151936)
    prompts = np.array([len(r.prompt) for r in reqs])
    outputs = np.array([r.max_new_tokens for r in reqs])
    assert (prompts % 128 == 0).all()
    assert prompts.min() >= 128 and prompts.max() <= 8192
    assert outputs.min() >= 16 and outputs.max() <= 1024
    # median 1020 before rounding up to 128: between 1020 and 1152 after
    assert 1020 <= np.median(prompts) <= 1152
    assert 124 <= np.median(outputs) <= 134
    assert prompts.max() == 8192 and outputs.max() == 1024   # the clipped tails are reached
    assert prompts.min() == 128 and outputs.min() == 16


def test_arrivals_fill_the_window_at_the_offered_rate():
    reqs = generator.make_requests(_mix(rate=3.0), SEED, 40, 1000)
    due = [r.due_s for r in reqs]
    assert len(reqs) == 120 and due[0] == 0.0
    assert all(b > a for a, b in zip(due, due[1:])) and due[-1] < 40


def test_mmpp_arrivals():
    mix = dict(CHAT, arrivals={"process": "mmpp", "calm_rate_per_s": 1.0,
                               "burst_rate_per_s": 10.0, "p_enter_burst": 0.15,
                               "p_exit_burst": 0.3})
    rate = generator.mean_rate(mix["arrivals"])
    assert rate == pytest.approx(1 / (1 / 3 / 10 + 2 / 3 / 1))
    reqs = generator.make_requests(mix, SEED, 30, 1000)
    assert len(reqs) == round(rate * 30)


def test_shapes_lists_what_set_up_warms():
    shp = generator.shapes(CHAT)
    assert shp["prompt_lengths"] == list(range(128, 8193, 128))
    assert shp["max_prompt"] == 8192 and shp["max_new_tokens"] == 1024
