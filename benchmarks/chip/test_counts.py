"""The yardstick's arithmetic, each function against a case worked by
hand, and the peak table."""
import pytest

import counts
import peaks


def test_mttkrp_counts_by_hand():
    # 10 nonzeros of a 3-mode tensor at rank 4: per nonzero 2*4 multiplies
    # and 4 adds -> 12 ops; bytes: 3 int32 indices + 1 f32 value + 2 rows
    # of 4 int8 words = 24 per nonzero, and 6 output rows of 4 f32 = 96
    assert counts.mttkrp_ops(10, 3, 4) == 120
    assert counts.mttkrp_bytes(10, 3, 4, 6) == 10 * 24 + 96
    # one sweep on dims (6, 5, 7): bytes 3*240 + (6+5+7)*16 = 1008 against
    # ops 3*120 = 360; at 1 op/s and 1 byte/s memory binds at 1008 s
    assert counts.mttkrp_least_s(10, (6, 5, 7), 4, 1.0, 1.0) == (1008.0,
                                                                 "memory")
    assert counts.mttkrp_least_s(10, (6, 5, 7), 4, 1.0, 1e6)[1] == "compute"


def test_sweep_ops_by_hand():
    # 4 MTTKRPs of 120 ops; Grams and solves 2 * 2*I*R^2 = 64*I over
    # I = 6+5+7 = 18 -> 1152; fit inner product 2*7*4 = 56
    assert counts.cp_als_sweep_ops(10, (6, 5, 7), 4) == 480 + 1152 + 56


def test_decoder_counts_by_hand():
    # d=8, 2 heads / 1 kv head of 4, ff 16: q 8*8, k and v 8*4 each, o 8*8,
    # ffn 3*8*16 -> 64 + 64 + 64 + 384 = 576 weights per layer
    dims = dict(layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
                d_ff=16, vocab=10)
    assert counts.layer_params(8, 2, 1, 4, 16) == 576
    # prompt of 3: 2 layers * (2*3*576 + 2*2*2*4*6) + 2*8*10
    assert counts.prefill_flops(3, **dims) == 2 * (3456 + 192) + 160


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["int8_ops"] == 393e12 and p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9 and "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
