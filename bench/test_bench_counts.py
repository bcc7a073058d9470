"""The peak table and the count functions against hand counts at the
shapes of the benchmark's two configurations."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import counts  # noqa: E402
import peaks  # noqa: E402

QWEN = {"n_layers": 4, "d_model": 2560, "n_heads": 20, "n_kv_heads": 20,
        "head_dim": 128, "d_ff": 6912, "vocab_size": 37984}
DEEPSEEK = {"n_layers": 4, "d_model": 7168, "n_heads": 56, "n_kv_heads": 8,
            "head_dim": 128, "d_ff": 19200, "vocab_size": 32256}


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks_for("TPU v9")


@pytest.mark.parametrize("dims,want", [
    # 4 x (4 x 2560^2 + 3 x 2560 x 6912) + 2560 x 37984
    (QWEN, 4 * (4 * 6_553_600 + 53_084_160) + 97_239_040),
    # 4 x (2 x 7168^2 + 2 x 7168 x 1024 + 3 x 7168 x 19200) + 7168 x 32256
    (DEEPSEEK, 4 * (102_760_448 + 14_680_064 + 412_876_800) + 231_211_008),
], ids=["qwen15_4b", "deepseek_coder_33b"])
def test_matmul_params(dims, want):
    assert counts.matmul_params(dims) == want


def test_train_flops_per_token_qwen():
    # 6 x 414,433,280 + 3 x 4 x 4 layers x 20 heads x 128 x 1025 / 2
    assert counts.train_flops_per_token(QWEN, 1024) == pytest.approx(
        2_486_599_680 + 62_976_000)


def test_decode_flops_deepseek():
    # 2 x 2,352,480,256 + 4 x 4 layers x 56 heads x 128 x 1000
    assert counts.decode_flops(DEEPSEEK, 1000) == 4_704_960_512 + 114_688_000


def test_rmsnorm_call_qwen_rows():
    call = counts.rmsnorm_call(12 * 1024, 2560)
    assert call == {"flops": 125_829_120, "bytes": 125_829_120 + 10_240}
    seconds, bound = counts.roofline_seconds(call, peaks.peaks_for(
        "TPU v5 lite"))
    assert bound == "memory"
    assert seconds == pytest.approx(125_839_360 / 819e9)


def test_decode_attention_call_deepseek():
    call = counts.decode_attention_call(DEEPSEEK, [100, 200])
    # q and out: 2 x 2 rows x 56 x 128 x 2 B; K and V: 2 x 300 x 8 x 128 x 2 B
    assert call == {"flops": 4 * 56 * 128 * 300,
                    "bytes": 57_344 + 1_228_800}
    assert counts.roofline_seconds(call, peaks.peaks_for(
        "TPU v5 lite"))[1] == "memory"
    assert counts.decode_attention_call(DEEPSEEK, []) == {"flops": 0,
                                                          "bytes": 0}
