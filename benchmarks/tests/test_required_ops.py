"""Required operations against hand-worked numbers."""
import pytest

from benchmarks.harness import modelcfg, required_ops
from benchmarks.harness.weights import param_count


def test_bench_1b_is_the_known_5_73_gflop_a_token():
    s = modelcfg.Sizes(vocab=32000, d_model=2048, layers=16, heads=16,
                       kv_heads=16, head_dim=128, d_ff=5632,
                       rope_theta=1e4, norm_eps=1e-5, tied=False)
    # 16 x (4 x 2048^2 + 3 x 2048 x 5632) + 2048 x 32000 = 887,619,584
    assert required_ops.matmul_params(s) == 887_619_584
    # 6 x 887.6M + 6 x 2048 x 2048 x 16 = 5.728 G
    assert required_ops.train_flops_per_token(s, 2048) == pytest.approx(
        5.728e9, rel=1e-3)


def test_mistral_width_cell():
    cfg = modelcfg.load_config("mistral-7b-v0.1-1chip")
    s = modelcfg.sizes(cfg)
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    assert required_ops.matmul_params(s) == (s.layers * per_layer
                                             + 4096 * 32000)
    want = 6 * (s.layers * per_layer + 131_072_000) \
        + 6 * 4096 * 4096 * s.layers
    assert required_ops.train_flops_per_token(s, 4096) == want
    # the head's share of the parameters is what the cell's why states
    total = param_count(s)
    assert total == s.layers * (per_layer + 2 * 4096) + 2 * 131_072_000 + 4096


def test_internlm2_parameters_are_the_published_1_889_b():
    cfg = modelcfg.load_config("internlm2-1.8b")
    assert param_count(modelcfg.sizes(cfg)) == cfg["parameters"] \
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


def test_peaks_table_refuses_an_unlisted_device():
    from benchmarks.harness import peaks
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.NoAccelerator):
        peaks.peaks_for("TPU v9 imaginary")
