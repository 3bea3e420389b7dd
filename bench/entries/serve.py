"""A served model under open-loop traffic, through ``ServeEngine``.

Set-up: seeded weights in the type they are served in, the engine, and
every shape the mix can send, warmed through the engine itself (each
whole-prompt length at each admission batch size, each last-chunk width
of a chunked prefill, each decode-block length).

Window: requests are submitted when due and the engine is stepped while
it has work; each request is timed from when it was due.  Requests due in
the window are then drained, for at most the mix's ``grace_s``; one that
has not finished by then, or ends in another status than OK, is failed.

Check: every request that finished is run through the plain reference
that the configuration names, with its served tokens (teacher forcing):
some thousands of served tokens, from prompts of every length the mix
sends, whole and chunked.
For each served token the gap by which its reference logit lies below
the reference's best is read; ``mean_gap``, their mean over all served
tokens, is compared with its limit.  A greedy engine that served what the
model says reads gaps of rounding only, where two logits nearly tie; a
wrong token reads the distance to the right one.  The widest gap and the
share of tokens on which the reference's best agrees are logged, not
compared: the widest is set by the closest tie a run happens to meet,
and its readings for the program and for the control overlap.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import generator, weights
from bench.entries.common import model_config
from bench.harness import Check, log, memory_peak, span

MIN_PAD = 1024          # the reference pads each sequence to a power of two
TRACE_S = 5.0           # the window's last seconds are traced; the profiler,
                        # slow to stop, stops after the window has closed


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def warm(eng, mix: dict, max_slots: int, chunk: int, decode_block: int, vocab: int):
    """Runs every program shape the mix can reach through ``eng``."""
    from repro.serve import Request  # noqa: PLC0415

    shp = generator.shapes(mix)
    rng = np.random.default_rng(0)
    prompt = lambda n: rng.integers(0, vocab, n).astype(np.int32)
    whole = [n for n in shp["prompt_lengths"] if n <= chunk]
    tails = sorted({n - chunk * ((n - 1) // chunk) for n in shp["prompt_lengths"] if n > chunk})
    for n in whole:                      # batched whole-prompt admissions
        for k in range(1, max_slots + 1):
            for _ in range(k):
                eng.submit(Request(tokens=prompt(n), max_new_tokens=1))
            eng.run()
    for t in tails:                      # chunked prefill, each last width
        eng.submit(Request(tokens=prompt(chunk + t), max_new_tokens=1))
        eng.run()
    steps = 1
    while steps <= min(decode_block, _next_pow2(shp["max_new_tokens"])):
        eng.submit(Request(tokens=prompt(whole[0] if whole else chunk), max_new_tokens=steps + 1))
        eng.run()
        steps *= 2
    eng.poll()


def _record_decode_calls(eng, calls: list):
    """Wraps the engine's decode-scan lookup so that each dispatch records
    its scan length and active-slot count."""
    lookup = eng._decode_scan_fn

    def wrapped(steps, sampling, max_top_k):
        fn = lookup(steps, sampling, max_top_k)

        def call(*args):
            calls.append((int(steps), int(np.asarray(args[4]).sum())))
            with span("bench.serve.decode_dispatch"):
                return fn(*args)

        return call

    eng._decode_scan_fn = wrapped


def run_cell(run, limits, clock, tracer, t_start, devices):
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    from repro.serve import Request, ServeEngine, Status  # noqa: PLC0415

    mix, sysd = run.traffic, run.config["system"]
    eng_cfg = sysd["engine"]
    cfg = model_config(run.config)
    dtype = jnp.dtype(sysd["weights_dtype"])
    params = weights.make(weights.layout(cfg, dtype), run.seed)
    jax.block_until_ready(params)
    reqs = generator.make_requests(mix, run.seed, run.seconds, cfg.vocab)
    eng = ServeEngine(params, cfg, **eng_cfg)
    calls: list = []
    _record_decode_calls(eng, calls)
    warm(eng, mix, eng_cfg["max_slots"], eng_cfg["prefill_chunk"],
         eng_cfg["decode_block"], cfg.vocab)
    run.setup_s = time.monotonic() - t_start
    log(f"[setup] setup_s={run.setup_s:.3f} {clock.describe()}")

    # -- window ---------------------------------------------------------
    temperature = float(mix.get("temperature", 0.0))
    n = len(reqs)
    rid, submitted = [None] * n, [None] * n
    results = {}
    steps_log = []
    compiled0 = clock.compiled
    t0 = time.monotonic()
    t_end = t0 + run.seconds
    due = [t0 + r.due_s for r in reqs]
    t_trace = t_end - min(TRACE_S, 0.3 * run.seconds)
    nxt = 0
    before = eng.stats()

    def submit_due(now):
        nonlocal nxt
        while nxt < n and due[nxt] <= now:
            r = reqs[nxt]
            with span("bench.serve.submit"):
                rid[nxt] = eng.submit(Request(tokens=r.prompt, max_new_tokens=r.max_new_tokens,
                                              temperature=temperature))
            submitted[nxt] = time.monotonic()
            nxt += 1

    def step():
        nonlocal before
        traced = tracer.active
        ncalls = len(calls)
        a = time.monotonic()
        with span("bench.serve.step"):
            eng.step()
        b = time.monotonic()
        after = eng.stats()
        d = {k: after.get(k, 0) - before.get(k, 0) for k in
             ("decode_dispatches", "prefill_dispatches", "decode_tokens", "prefill_tokens")}
        steps_log.append(dict(d, t0=a, t1=b, calls=calls[ncalls:], traced=traced and tracer.active,
                              occupied=after["slots_occupied"], queue=after["queue_depth"]))
        before = after
        for k, res in eng.poll().items():
            results[k] = res

    def pending():
        return nxt > len(results) or before.get("queue_depth", 0) or before.get("slots_occupied", 0)

    while True:
        now = time.monotonic()
        if now >= t_end:
            break
        if now >= t_trace and not tracer.done:
            tracer.start()
        submit_due(now)
        if pending():
            step()
        else:
            wake = min([t_end, t_trace if not tracer.done else t_end]
                       + ([due[nxt]] if nxt < n else []))
            time.sleep(max(0.0, wake - time.monotonic()))
    tracer.stop()
    run.window_s = time.monotonic() - t0
    run.compiles_in_window = clock.compiled - compiled0
    submit_due(float("inf"))           # due in the window, submitted late
    deadline = time.monotonic() + float(mix.get("grace_s", 60))
    while len(results) < n and time.monotonic() < deadline:
        step()
    run.memory_peak_bytes = memory_peak(devices)
    run.attempted = n
    records = []
    for i, r in enumerate(reqs):
        res = results.get(rid[i])
        ok = res is not None and res.status is Status.OK and len(res.tokens) == r.max_new_tokens
        records.append(dict(due=due[i], submitted=submitted[i], ok=ok,
                            first=res.first_token_at if res else None,
                            finished=res.finished_at if res else None,
                            tokens=np.asarray(res.tokens) if res else np.zeros(0, np.int32),
                            prompt=r.prompt))
    run.failed = sum(not rec["ok"] for rec in records)
    late = [rec["submitted"] - rec["due"] for rec in records]
    ttft = [(rec["first"] or deadline) - rec["due"] for rec in records]
    log(f"[window] {n} requests due over {run.seconds} s; failed {run.failed}; "
        f"generator lateness max {max(late) * 1e3:.1f} ms, "
        f"p90 {generator.percentile(late, 90) * 1e3:.1f} ms; time to first token "
        f"p50 {generator.percentile(ttft, 50) * 1e3:.1f} ms, p90 {generator.percentile(ttft, 90) * 1e3:.1f} ms; "
        f"compiles in window {run.compiles_in_window}")
    run.data.update(records=records, steps=steps_log, t0=t0, t_end=t_end,
                    max_slots=eng_cfg["max_slots"], cfg=cfg, grace_end=deadline,
                    params=params)
    del eng
    gc.collect()
    with span("bench.check"):
        run.checks = check(run, params, records, limits)


def pad_length(n: int, most: int) -> int:
    """The reference's padded length for a sequence of ``n`` tokens: a
    power of two from ``MIN_PAD``, at most ``most`` (few shapes to
    compile)."""
    return min(most, max(MIN_PAD, _next_pow2(n)))


def reference_gaps(run, params, records, picked, dtype=None):
    """For each picked request, the gap below its best logit of every
    served token in the reference that the configuration names; with
    ``dtype`` also the gap of the token that the reference computed in
    ``dtype`` puts first.  Returns two lists."""
    ref = run.reference
    spec = ref.Spec.from_config(run.config)
    shp = generator.shapes(run.traffic)
    most, out_len = shp["max_prompt"] + shp["max_new_tokens"], shp["max_new_tokens"]
    served, lower = [], []
    for i in picked:
        p, t = records[i]["prompt"], records[i]["tokens"]
        seq = np.zeros(pad_length(len(p) + len(t), most), np.int32)
        seq[: len(p) + len(t) - 1] = np.concatenate([p, t[:-1]])
        idx = np.full(out_len, len(p) - 1, np.int32)
        idx[: len(t)] = np.arange(len(p) - 1, len(p) - 1 + len(t))
        logits = np.asarray(ref.logits_at(spec, params, seq, idx))[: len(t)]
        best = logits.max(-1)
        served.append(best - logits[np.arange(len(t)), t])
        if dtype is not None:
            low = np.asarray(ref.logits_at(spec, params, seq, idx, dtype=dtype))[: len(t)]
            lower.append(best - logits[np.arange(len(t)), low.argmax(-1)])
    return served, lower


def checked(records: list) -> list:
    """Indices of the requests the check runs: every one that finished."""
    return [i for i, r in enumerate(records) if r["ok"]]


def check(run, params, records, limits) -> list:
    picked = checked(records)
    if not picked:
        return [Check("mean_gap", float("nan"), limits["mean_gap"])]
    t = time.monotonic()
    served, _ = reference_gaps(run, params, records, picked)
    gaps = np.concatenate(served)
    log(f"[check] {len(picked)} requests, {gaps.size} served tokens in "
        f"{time.monotonic() - t:.1f} s; "
        f"prompts {sorted(len(records[i]['prompt']) for i in picked)}; "
        f"widest gap {gaps.max():.5f}; reference argmax agreement {(gaps == 0).mean():.5f}")
    return [Check("mean_gap", float(gaps.mean()), limits["mean_gap"])]
