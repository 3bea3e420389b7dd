"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) names an arrival process and the
length distributions; this module turns it, a seed and a window length
into requests.  Every run offers the same set of gaps and of prompt and
output lengths, taken at fixed quantiles of the mix's distributions; the
run seed draws their order (which gap follows which, which request gets
which lengths) and the prompts' token ids.  Seeds then differ in when
each request comes and what it asks for, never in how much work a window
offers.  Lengths may be rounded up to a multiple (a bound on the number
of distinct prefill shapes).

Arrival processes (the logic of ``repro.serve.load``'s Poisson and bursty
traces, copied so that the yardstick does not move with the program):

* ``poisson``: exponential gaps at ``rate_per_s``.
* ``mmpp``: two-state Markov-modulated Poisson; a calm/burst state flips
  per arrival (``p_enter_burst`` / ``p_exit_burst``) and each state has its
  own rate (``calm_rate_per_s`` / ``burst_rate_per_s``).

Length distributions: ``lognormal`` (``median``, ``sigma``) and
``uniform`` (inclusive ``min``..``max``), both clipped to ``[min, max]``.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    """One request of a mix: when it is due (seconds after the window
    opens), its prompt, and how many tokens it asks for."""

    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at fixed quantiles of ``spec``'s clipped distribution,
    rounded up to ``spec["round_up"]`` (sorted ascending)."""
    u = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
        vals = np.ceil(vals)
    elif spec["dist"] == "uniform":
        vals = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    vals = np.clip(vals, lo, hi).astype(np.int64)
    step = int(spec.get("round_up", 1))
    return (-(-vals // step) * step).astype(np.int64)


def gaps(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Inter-arrival gaps in seconds, in arrival order."""
    unit = -np.log1p(-_quantiles(n))  # Exp(1) at fixed quantiles
    unit = unit[rng.permutation(n)]
    if spec["process"] == "poisson":
        return unit / float(spec["rate_per_s"])
    if spec["process"] == "mmpp":
        out = np.empty(n)
        burst = False
        flips = rng.random(n)
        for i in range(n):
            if burst and flips[i] < spec["p_exit_burst"]:
                burst = False
            elif not burst and flips[i] < spec["p_enter_burst"]:
                burst = True
            rate = spec["burst_rate_per_s"] if burst else spec["calm_rate_per_s"]
            out[i] = unit[i] / float(rate)
        return out
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def mean_rate(spec: dict) -> float:
    """Long-run arrivals per second of an arrival spec."""
    if spec["process"] == "poisson":
        return float(spec["rate_per_s"])
    # stationary share of arrivals in the burst state
    share = spec["p_enter_burst"] / (spec["p_enter_burst"] + spec["p_exit_burst"])
    mean_gap = share / spec["burst_rate_per_s"] + (1 - share) / spec["calm_rate_per_s"]
    return 1.0 / mean_gap


def make_requests(mix: dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    """The requests of one run of an open-loop mix, due within
    ``[0, seconds)``, in due order: the mix's gaps and lengths in an order
    drawn from ``seed``, and token ids drawn from it."""
    arr = mix["arrivals"]
    n = max(1, round(mean_rate(arr) * seconds))
    rng = np.random.default_rng(seed)
    g = gaps(arr, n, rng)
    # n arrivals spread over exactly the window: the offered rate is n/seconds
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]]) * seconds / g.sum()
    prompt_len = lengths(mix["prompt"], n)[rng.permutation(n)]
    new_tokens = lengths(mix["output"], n)[rng.permutation(n)]
    tokens = rng
    return [
        Req(due_s=float(due[i]),
            prompt=tokens.integers(0, vocab, int(prompt_len[i])).astype(np.int32),
            max_new_tokens=int(new_tokens[i]))
        for i in range(n)
    ]


def shapes(mix: dict) -> dict:
    """Every prompt length a mix can send and the largest output it asks
    for: what set-up has to warm."""
    p = mix["prompt"]
    step = int(p.get("round_up", 1))
    lo = -(-int(p["min"]) // step) * step
    hi = -(-int(p["max"]) // step) * step
    return {"prompt_lengths": list(range(lo, hi + 1, step)),
            "max_prompt": hi, "max_new_tokens": int(mix["output"]["max"])}


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, float), q))
