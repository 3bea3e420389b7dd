"""Required-work counts against hand computations at the configurations'
widths, and the peaks table."""

import pytest

from bench import work

# qwen2-1.5b: 28 layers, d_model 1536, 12/2 heads of 128, d_ff 8960,
# vocab 151936, q/k/v biases, tied head.  smollm-135m: 30 layers, 576,
# 9/3 heads of 64, d_ff 1536, vocab 49152, tied.
QWEN = work.Widths(28, 1536, 12, 2, 128, 8960, 151936, qkv_bias=True)
SMOL = work.Widths(30, 576, 9, 3, 64, 1536, 49152)


def test_param_counts_by_hand():
    # per layer: q,o 1536*12*128 each, k,v 1536*2*128 each, biases
    # 12*128 + 2*2*128, MLP 3*1536*8960, two norms; table + final norm
    qwen_layer = 2 * 2_359_296 + 2 * 393_216 + 2_048 + 41_287_680 + 3_072
    assert work.param_count(QWEN) == 28 * qwen_layer + 233_373_696 + 1_536 == 1_543_714_304
    smol_layer = 2 * 331_776 + 2 * 110_592 + 2_654_208 + 1_152
    assert work.param_count(SMOL) == 30 * smol_layer + 28_311_552 + 576 == 134_515_008


def test_param_counts_match_the_program():
    from repro.configs import get_config
    from repro.models.config import count_params

    for name in ("qwen2-1.5b", "smollm-135m"):
        cfg = get_config(name)
        assert work.param_count(work.Widths.of(cfg)) == count_params(cfg)


def test_taylor_forward_flops_at_d64():
    # D = 1 + 64 + 64*65/2 = 2145 features; a moment read or update is
    # 2*D*(64+1) plus the 2080 products of sym(x⊗x): 280,930 per head
    # and token, for 9 query + 3 kv heads over the 4096 - 128 tokens past
    # the first chunk; intra-chunk: 9 heads * 32 chunks * 128*129/2 pairs
    # * (2*64 + 3 + 2*65).
    per_token = 12 * (2 * 2145 * 65 + 2080)
    intra = 9 * 32 * 8256 * 261
    assert work.taylor_fwd_flops(SMOL, 4096) == intra + 3968 * per_token == 13_997_349_888
    assert work.taylor_bwd_flops(SMOL, 4096) == 2 * 13_997_349_888
    # bf16 q (9*64), k and v (3*128), output (9*64), per token
    assert work.taylor_fwd_bytes(SMOL, 4096) == 4096 * 2 * 1536


def test_decode_bytes_per_step():
    # per kv head: n0 1 + s0 128 + z1 128 + s1 128² + z2 128² + s2 128³
    state = 28 * 2 * (1 + 128 + 128 + 2 * 16_384 + 2_097_152) * 4
    assert work.state_bytes_per_slot(QWEN) == state == 477_159_648
    assert work.decode_step_bytes(QWEN, 6) == 2 * 1_543_714_304 + 6 * 2 * state
    assert work.decode_step_bytes(QWEN, 0) == 3_087_428_608


def test_forward_flops_per_token():
    # two per parameter, plus per layer 14 heads of 2*8385*129 + 8256
    taylor = 28 * 14 * (2 * 8385 * 129 + 8256)
    assert work.fwd_flops_per_token(QWEN) == 2 * 1_543_714_304 + taylor == 3_938_690_320


def test_train_step_flops():
    six_n = 6 * 134_515_008 * 8 * 4096
    assert work.train_step_flops(SMOL, 8, 4096) == six_n + 3 * 30 * 8 * 13_997_349_888


def test_roofline_share_names_its_bound():
    peak = work.peaks("TPU v5 lite")
    assert work.roofline_share(197e12, 0, 2.0, peak) == (50.0, "flops")
    share, bound = work.roofline_share(0, 819e9, 4.0, peak)
    assert (share, bound) == (25.0, "bytes")


def test_unknown_device_kind_is_an_error():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        work.peaks("TPU v9 imaginary")
