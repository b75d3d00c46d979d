"""Required operations against hand-worked numbers."""
import pytest

from benchmarks.harness import modelcfg, required_ops

dense = modelcfg.load_model({"model": "dense_gqa"})


def test_bench_1b_is_the_known_5_73_gflop_a_token():
    s = dense.Sizes(vocab=32000, d_model=2048, layers=16, heads=16,
                       kv_heads=16, head_dim=128, d_ff=5632,
                       rope_theta=1e4, norm_eps=1e-5, tied=False)
    # 16 x (4 x 2048^2 + 3 x 2048 x 5632) + 2048 x 32000 = 887,619,584
    assert dense.matmul_params(s) == 887_619_584
    # 6 x 887.6M + 6 x 2048 x 2048 x 16 = 5.728 G
    assert dense.train_flops_per_token(s, 2048) == pytest.approx(
        5.728e9, rel=1e-3)


def test_mistral_width_cell():
    cfg = modelcfg.load_config("mistral-7b-v0.1-1chip")
    s = dense.sizes(cfg)
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    assert dense.matmul_params(s) == (s.layers * per_layer
                                             + 4096 * 32000)
    want = 6 * (s.layers * per_layer + 131_072_000) \
        + 6 * 4096 * 4096 * s.layers
    assert dense.train_flops_per_token(s, 4096) == want
    # the head's share of the parameters is what the cell's why states
    total = dense.param_count(s)
    assert total == s.layers * (per_layer + 2 * 4096) + 2 * 131_072_000 + 4096


def test_internlm2_parameters_are_the_published_1_889_b():
    cfg = modelcfg.load_config("internlm2-1.8b")
    assert dense.param_count(dense.sizes(cfg)) == cfg["parameters"] \
        == 1_889_110_016


def test_flash_call_counts_two_and_five_matmuls():
    one = required_ops.flash_call(2, 32, 8, 4096, 128)
    mm = 2 * (4096 * 4096 / 2) * 128 * 32 * 2
    assert one["fwd_flops"] == 2 * mm and one["bwd_flops"] == 5 * mm
    q, kv = 2 * 32 * 4096 * 128 * 2, 2 * 8 * 4096 * 128 * 2
    assert one["fwd_bytes"] == 2 * q + 2 * kv + 2 * 32 * 4096 * 4
    t, bound = required_ops.roofline_seconds(
        one["fwd_flops"], one["fwd_bytes"],
        {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "ops" and t == pytest.approx(one["fwd_flops"] / 197e12)


def test_paged_decode_call_is_the_batch_cell_s_0_45_gb_a_step():
    # 8 lanes at 576 positions, 24 layers, 8 kv heads and 16 heads of 128:
    # keys and values 2 x 4608 x 1024 x 2 bytes a layer, queries and
    # outputs 2 x 8 x 2048 x 2; PERF.md's hand count (0.45 GB, 32 % at
    # 1.67 ms)
    one = required_ops.paged_decode_call(8 * 576, 8, 24, 1024, 2048)
    assert one["bytes"] == 24 * (2 * 4608 * 1024 * 2 + 2 * 8 * 2048 * 2) \
        == 454_557_696
    assert one["flops"] == 2 * 2 * 4608 * 2048 * 24
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = required_ops.roofline_seconds(one["flops"], one["bytes"],
                                             peaks)
    assert bound == "bytes" and t == pytest.approx(0.555e-3, rel=1e-3)
    assert required_ops.roofline_share(
        one["flops"], one["bytes"], 1.67e-3, peaks) == pytest.approx(
            33.2, abs=0.1)


def test_peaks_table_refuses_an_unlisted_device():
    from benchmarks.harness import peaks
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.NoAccelerator):
        peaks.peaks_for("TPU v9 imaginary")
