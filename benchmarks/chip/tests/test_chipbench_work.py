"""Work counts and the peak table, against hand counts at a tiny size."""
import pytest

from chip import peaks, work

# d=8, 2 heads of 4, ff=16, vocab 10, 2 layers
C = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=2,
         intermediate_size=16, vocab_size=10, num_hidden_layers=2)


def test_matmul_params_by_hand():
    # per layer: q,k,v,o 4 * 8*8 = 256, mlp 3 * 8*16 = 384; head 8*10
    assert work.layer_matmul_params(C) == 2 * 640
    assert work.matmul_params(C) == 2 * 640 + 80


def test_train_flops_per_token_by_hand():
    # S = 3: causal pairs 6, so 2 keys per token on average
    # 6 * 1360 + 3 * (4 * L * H * hd) * 6 / 3 = 8160 + 12 * 2 * 2 * 4 * 2
    assert work.train_flops_per_token(C, 3) == 8160 + 384


def test_prefill_flops_by_hand():
    # layers 2 * 2 * 640 * 3, attention 4 * 2 * 2 * 4 * 6, head 2 * 8 * 10
    assert work.prefill_flops(C, 3) == 7680 + 384 + 160


def test_decode_counts_by_hand():
    # 3 active slots attending 10 positions in all, bf16
    assert work.decode_flops(C, 3, 10) == 2 * 1360 * 3 + 4 * 2 * 2 * 4 * 10
    per_pos = 2 * 2 * 2 * 4 * 2          # L * (k, v) * KV * hd * bytes
    weights = (1360 + (2 * 2 + 1) * 2 * 8) * 2
    assert work.decode_least_bytes(C, 2, 3, 10) == \
        weights + 3 * 8 * 2 + 10 * per_pos + 3 * per_pos


def test_flash_fwd_by_hand():
    flops, nbytes = work.flash_fwd(C, rows=1, S=3, itemsize=2)
    assert flops == 4 * 2 * 1 * 2 * 4 * 6
    assert nbytes == 2 * 1 * 3 * (2 * 2 + 2 * 2) * 4 * 2


@pytest.mark.parametrize("slots,max_len", [(1, 16), (8, 1280), (16, 64)])
def test_least_decode_bytes_never_exceed_the_program_step(slots, max_len):
    """Whatever is live, the least bytes stay at or under what the
    repository's gather-the-pool step reads."""
    big = dict(C, hidden_size=2048, num_attention_heads=32,
               num_key_value_heads=32, intermediate_size=5632,
               vocab_size=100352, num_hidden_layers=24)
    read = work.decode_program_bytes(big, 2, slots, max_len)
    for active in range(1, slots + 1):
        for live in (active, active * max_len // 2, active * (max_len - 1)):
            assert work.decode_least_bytes(big, 2, active, live) <= read


def test_known_device_has_its_published_peaks():
    pk = peaks.peak("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v99")


def test_roofline_names_its_bound():
    pk = peaks.peak("TPU v5 lite")
    assert peaks.roofline_s(197e12, 1.0, pk) == (1.0, "compute")
    assert peaks.roofline_s(1.0, 819e9, pk) == (1.0, "bytes")
