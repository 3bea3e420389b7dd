"""Serving engine: generation, taylor-vs-kv cache behaviour, long context,
continuous batching (slot admission/eviction, scan-decode parity)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import lm_init
from repro.models.lm import _cfg_runs, lm_apply, lm_init_caches, lm_prefill
from repro.serve import Request, ServeEngine, generate, generate_loop


@pytest.mark.parametrize("backend", ["taylor", "softmax"])
def test_generate_greedy_matches_teacher_forcing(backend, rng):
    cfg = get_reduced("qwen2-1.5b").replace(attention=backend)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    toks = generate(params, {"tokens": prompt}, cfg, steps=6)
    assert toks.shape == (2, 6)
    # re-run the full sequence through the parallel forward; greedy argmax at
    # each position must reproduce the generated tokens.
    full = jnp.concatenate([prompt, toks], axis=1)
    logits, _ = lm_apply(params, {"tokens": full}, cfg)
    for i in range(6):
        expect = jnp.argmax(logits[:, 16 + i - 1], axis=-1)
        np.testing.assert_array_equal(np.asarray(expect), np.asarray(toks[:, i]))


def test_taylor_cache_is_constant_size(rng):
    """The paper's O(1) decode: cache bytes must not grow with context."""
    cfg = get_reduced("granite-20b")  # taylor backend, MQA
    small = lm_init_caches(cfg, batch=2, n_max=64)
    large = lm_init_caches(cfg, batch=2, n_max=4096)

    def nbytes(t):
        return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(t))

    assert nbytes(small) == nbytes(large)

    cfg_sm = cfg.replace(attention="softmax")
    kv_small = lm_init_caches(cfg_sm, batch=2, n_max=64)
    kv_large = lm_init_caches(cfg_sm, batch=2, n_max=4096)
    assert nbytes(kv_large) > 32 * nbytes(kv_small)  # KV cache grows linearly


def test_prefill_state_equals_incremental_decode_state(rng):
    """Chunked prefill state == state after token-by-token decode."""
    cfg = get_reduced("smollm-135m")
    params = lm_init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 32)), jnp.int32)
    logits_pre, caches_pre = lm_prefill(params, {"tokens": toks}, cfg, n_max=40)

    from repro.models.lm import lm_decode_step

    caches = lm_init_caches(cfg, 1, 40, jnp.dtype(cfg.dtype))
    for i in range(32):
        logits_dec, caches = lm_decode_step(
            params, toks[:, i], caches, jnp.asarray(i, jnp.int32), cfg
        )
    np.testing.assert_allclose(
        np.asarray(logits_pre), np.asarray(logits_dec), atol=2e-3, rtol=2e-3
    )


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["taylor", "softmax"])
def test_scan_decode_matches_per_token_loop(backend, rng):
    """The compiled block-decode engine must emit token-identical greedy
    output to the old one-dispatch-per-token loop."""
    cfg = get_reduced("qwen2-1.5b").replace(attention=backend)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    old = np.asarray(generate_loop(params, {"tokens": prompt}, cfg, steps=8))
    new = np.asarray(generate(params, {"tokens": prompt}, cfg, steps=8))
    np.testing.assert_array_equal(old, new)


@pytest.mark.parametrize("backend", ["taylor", "softmax"])
def test_mixed_length_continuous_batching(backend, rng):
    """Requests with different prompt lengths / budgets decode together;
    each must match its own solo run exactly (slots never interact)."""
    cfg = get_reduced("qwen2-1.5b").replace(attention=backend)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prompts = [
        np.asarray(rng.integers(0, cfg.vocab, (n,)), np.int32)
        for n in (16, 9, 21)
    ]
    budgets = (6, 9, 4)
    eng = ServeEngine(params, cfg, max_slots=2, n_max=64, decode_block=3)
    rids = [
        eng.submit(Request(tokens=p, max_new_tokens=b))
        for p, b in zip(prompts, budgets)
    ]
    outs = eng.run()
    for p, b, rid in zip(prompts, budgets, rids):
        solo = np.asarray(
            generate_loop(params, {"tokens": jnp.asarray(p)[None]}, cfg, steps=b)
        )[0]
        np.testing.assert_array_equal(outs[rid], solo)


def test_late_admitted_request_matches_solo(rng):
    """A request submitted while the batch is mid-flight is admitted into a
    freed slot and still reproduces its solo-run tokens."""
    cfg = get_reduced("qwen2-1.5b")  # taylor backend
    params = lm_init(jax.random.PRNGKey(0), cfg)
    p_busy = np.asarray(rng.integers(0, cfg.vocab, (2, 16)), np.int32)
    p_late = np.asarray(rng.integers(0, cfg.vocab, (11,)), np.int32)
    eng = ServeEngine(params, cfg, max_slots=2, n_max=64, decode_block=2)
    eng.submit(Request(tokens=p_busy[0], max_new_tokens=12))
    eng.submit(Request(tokens=p_busy[1], max_new_tokens=4))
    eng.step()  # both slots busy, several tokens in
    rid_late = eng.submit(Request(tokens=p_late, max_new_tokens=7))
    outs = eng.run()
    solo = np.asarray(
        generate_loop(params, {"tokens": jnp.asarray(p_late)[None]}, cfg, steps=7)
    )[0]
    np.testing.assert_array_equal(outs[rid_late], solo)


@pytest.mark.parametrize("backend", ["taylor", "softmax"])
def test_slot_eviction_and_reuse(backend, rng):
    """More requests than slots: slots are retired, cleared, and re-admitted;
    every request (including ones decoding in a reused slot) matches solo."""
    cfg = get_reduced("smollm-135m").replace(attention=backend)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prompts = [
        np.asarray(rng.integers(0, cfg.vocab, (n,)), np.int32)
        for n in (8, 12, 10, 15, 7)
    ]
    eng = ServeEngine(params, cfg, max_slots=2, n_max=48, decode_block=4)
    rids = [eng.submit(Request(tokens=p, max_new_tokens=5)) for p in prompts]
    outs = eng.run()
    assert set(outs) == set(rids)
    for p, rid in zip(prompts, rids):
        solo = np.asarray(
            generate_loop(params, {"tokens": jnp.asarray(p)[None]}, cfg, steps=5)
        )[0]
        np.testing.assert_array_equal(outs[rid], solo)


def test_eos_stops_slot_early(rng):
    """A slot that emits its eos_id stops there (eos included in output)."""
    cfg = get_reduced("qwen2-1.5b")
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray(rng.integers(0, cfg.vocab, (16,)), np.int32)
    solo = np.asarray(
        generate_loop(params, {"tokens": jnp.asarray(prompt)[None]}, cfg, steps=8)
    )[0]
    eos = int(solo[3])  # greedy emits this at step 3: engine must stop there
    eng = ServeEngine(params, cfg, max_slots=2, n_max=64, decode_block=8)
    rid = eng.submit(Request(tokens=prompt, max_new_tokens=8, eos_id=eos))
    out = eng.run()[rid]
    first_eos = int(np.argmax(solo == eos))
    np.testing.assert_array_equal(out, solo[: first_eos + 1])


def test_per_slot_sampling_topk1_equals_greedy(rng):
    """top_k=1 sampling collapses to argmax, so a sampled slot with k=1 and
    a greedy slot must produce identical tokens from the same prompt."""
    cfg = get_reduced("qwen2-1.5b")
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray(rng.integers(0, cfg.vocab, (16,)), np.int32)
    eng = ServeEngine(params, cfg, max_slots=2, n_max=64, decode_block=4)
    r_greedy = eng.submit(Request(tokens=prompt, max_new_tokens=6))
    r_top1 = eng.submit(
        Request(tokens=prompt, max_new_tokens=6, temperature=0.7, top_k=1)
    )
    outs = eng.run()
    np.testing.assert_array_equal(outs[r_greedy], outs[r_top1])


def test_sampled_tokens_in_vocab(rng):
    """Temperature/top-k sampling emits valid vocab ids of the right count."""
    cfg = get_reduced("qwen2-1.5b")
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray(rng.integers(0, cfg.vocab, (16,)), np.int32)
    eng = ServeEngine(
        params, cfg, max_slots=2, n_max=64, decode_block=4,
        rng=jax.random.PRNGKey(7),
    )
    rid = eng.submit(
        Request(tokens=prompt, max_new_tokens=9, temperature=1.3, top_k=5)
    )
    out = eng.run()[rid]
    assert out.shape == (9,)
    assert ((out >= 0) & (out < cfg.vocab)).all()


def test_submit_rejects_mismatched_kv_src_shape(rng):
    """The slot cache preallocates kv_src at the config's source length;
    a request with a different image length must fail loudly at submit,
    not crash in write_slot mid-flight."""
    cfg = get_reduced("llama-3.2-vision-11b")
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray(rng.integers(0, cfg.vocab, (8,)), np.int32)
    eng = ServeEngine(params, cfg, max_slots=2, n_max=64)
    bad_img = np.zeros((1, cfg.n_image_tokens + 4, cfg.vision_dim), np.float32)
    with pytest.raises(ValueError, match="image_embeds"):
        eng.submit(Request(tokens=prompt, max_new_tokens=4,
                           extras={"image_embeds": bad_img}))
    with pytest.raises(ValueError, match="image_embeds"):
        eng.submit(Request(tokens=prompt, max_new_tokens=4))  # missing


def test_vlm_generation_uses_image(rng):
    cfg = get_reduced("llama-3.2-vision-11b")
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (1, 12)), jnp.int32)
    img1 = jnp.asarray(rng.normal(size=(1, cfg.n_image_tokens, cfg.vision_dim)), jnp.float32)
    img2 = jnp.asarray(rng.normal(size=(1, cfg.n_image_tokens, cfg.vision_dim)), jnp.float32)
    t1 = generate(params, {"tokens": prompt, "image_embeds": img1}, cfg, steps=4)
    t2 = generate(params, {"tokens": prompt, "image_embeds": img2}, cfg, steps=4)
    assert not np.array_equal(np.asarray(t1), np.asarray(t2))


def _long_run_hybrid():
    """Two groups of a taylor run of length 2 then a window layer: the
    layer scans index both the group and the position inside a run."""
    return get_reduced("qwen2-1.5b").replace(
        pattern=("attn",) * 3, n_groups=2, attention="taylor",
        attn_window=16, attention_schedule={2: "softmax_window"},
    )


def _arch(arch):
    """``taylor`` / ``taylor_pallas`` (the decode kernel, interpreted) or
    the long-run hybrid."""
    if arch == "long_run_hybrid":
        return _long_run_hybrid()
    impl = "pallas" if arch == "taylor_pallas" else "auto"
    return get_reduced("qwen2-1.5b").replace(attn_impl=impl)


ARCHS = ["taylor", "long_run_hybrid", "taylor_pallas"]


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_scan_holds_inactive_slots_bit_for_bit(arch, rng):
    """A decode dispatch with a slot inactive at dispatch: that slot's
    cache leaves come back bit-identical to its input, and the active
    slots emit the tokens of a per-token ``decode_step`` loop."""
    from repro.serve.engine import decode_scan, decode_step  # noqa: PLC0415

    cfg = _arch(arch)
    if arch == "long_run_hybrid":
        assert any(rl > 1 for _, _, rl in _cfg_runs(cfg))
    params = lm_init(jax.random.PRNGKey(0), cfg)
    slots, n, steps = 3, 8, 5
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (slots, n)), jnp.int32)
    logits, caches = lm_prefill(params, {"tokens": prompt}, cfg, n_max=32)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    pos = jnp.full((slots,), n, jnp.int32)
    active = jnp.asarray([True, False, True])
    before = jax.tree.map(np.asarray, caches)

    # the per-token loop advances every row (no keep mask)
    loop_caches, loop_tok, loop_toks = caches, token, []
    for i in range(steps):
        lg, loop_caches = decode_step(params, loop_tok, loop_caches, pos + i, cfg)
        loop_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        loop_toks.append(np.asarray(loop_tok))
    loop_toks = np.stack(loop_toks)

    out, _, out_pos, _, _, toks, mask = decode_scan(
        params, caches, token, pos, active,
        jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
        jnp.full((slots,), -1, jnp.int32), jax.random.PRNGKey(1),
        cfg, steps, sampling=False, max_top_k=0,
    )
    keep = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(mask), np.tile(keep, (steps, 1)))
    np.testing.assert_array_equal(np.asarray(toks)[:, keep], loop_toks[:, keep])
    np.testing.assert_array_equal(np.asarray(out_pos), np.where(keep, n + steps, n))
    for axis, part in ((2, "group"), (0, "tail")):
        for got, was in zip(jax.tree.leaves(out[part]),
                            jax.tree.leaves(before[part])):
            np.testing.assert_array_equal(
                np.take(np.asarray(got), [1], axis=axis),
                np.take(was, [1], axis=axis),
            )


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_keep_mask(arch, rng):
    """``keep=None`` (the speculative draft loop's call) and an all-True
    ``keep`` give bit-identical logits and caches; a partial ``keep``
    changes nothing in the kept rows and leaves the other rows' caches
    exactly as they went in."""
    from repro.models.lm import lm_decode_step  # noqa: PLC0415

    cfg = _arch(arch)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    b, n = 3, 8
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (b, n)), jnp.int32)
    _, caches = lm_prefill(params, {"tokens": prompt}, cfg, n_max=32)
    tok = prompt[:, -1]
    pos = jnp.full((b,), n, jnp.int32)
    step = jax.jit(functools.partial(lm_decode_step, cfg=cfg))
    ref_logits, ref = step(params, tok, caches, pos)
    all_logits, all_kept = step(params, tok, caches, pos,
                                keep=jnp.ones((b,), bool))
    np.testing.assert_array_equal(np.asarray(all_logits), np.asarray(ref_logits))
    for x, y in zip(jax.tree.leaves(all_kept), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    keep = np.asarray([False, True, True])
    part_logits, part = step(params, tok, caches, pos, keep=jnp.asarray(keep))
    np.testing.assert_array_equal(np.asarray(part_logits), np.asarray(ref_logits))
    for axis, name in ((2, "group"), (0, "tail")):
        for got, new, old in zip(jax.tree.leaves(part[name]),
                                 jax.tree.leaves(ref[name]),
                                 jax.tree.leaves(caches[name])):
            want = np.where(
                keep.reshape([-1 if a == axis else 1 for a in range(got.ndim)]),
                np.asarray(new), np.asarray(old))
            np.testing.assert_array_equal(np.asarray(got), want)


def test_engine_decode_kernel_gives_the_xla_tokens(rng):
    """Greedy serving through the Pallas decode kernel (forced, so the
    interpreter runs it here) emits the tokens of the XLA recurrence, with
    slots idle inside dispatches; ``stats()`` counts the kernel's layers."""
    cfg = get_reduced("qwen2-1.5b")  # taylor backend, 3 layers
    params = lm_init(jax.random.PRNGKey(0), cfg)
    prompts = [np.asarray(rng.integers(0, cfg.vocab, (n,)), np.int32)
               for n in (12, 7, 16)]
    budgets = (9, 4, 6)
    tokens, layers = {}, {}
    for impl in ("xla", "pallas"):
        eng = ServeEngine(params, cfg.replace(attn_impl=impl), max_slots=2,
                          n_max=64, decode_block=4)
        rids = [eng.submit(Request(tokens=p, max_new_tokens=b))
                for p, b in zip(prompts, budgets)]
        outs = eng.run()
        tokens[impl] = [outs[r].tolist() for r in rids]
        layers[impl] = eng.stats()["decode_kernel_layers"]
    assert tokens["pallas"] == tokens["xla"]
    assert layers == {"xla": 0, "pallas": cfg.n_layers}
