"""The roofline arithmetic: byte counts for both configurations, the
optimizer's row-transfer count, and the table of peaks."""

import pytest

from benchmark.builders import dlrm, synthetic
from benchmark.harness import roofline, spec
from benchmark.harness.built import mlp_train_flops


def _built(name):
    config = spec.load_json(f"benchmark/configs/{name}.json")
    module = {"synthetic": synthetic, "dlrm": dlrm}[config["builder"]]
    return module.build(config, None, True), config


def test_tiny_v3_bytes_adagrad_seven_transfers_hotness_weighted():
    built, _ = _built("synthetic-tiny-v3")
    widths = [built.tables[t][1] for t in built.table_map]
    # inputs of Tiny V3: three shared tables with hotness 1 and 10 (widths 8,
    # 16, 16), one 16-wide one-hot, then 16+10+4 of width 8 and 2+19 of 16
    assert sum(built.hotness) == 3 * 11 + 1 + 30 + 21 == 85
    rows_bytes = 4 * ((8 + 16 + 16) * 11 + 16 + 30 * 8 + 21 * 16)
    assert rows_bytes == 4 * sum(w * h for w, h in zip(widths, built.hotness))
    assert roofline.embedding_bytes_per_sample(
        widths, built.hotness, "adagrad") == 7 * rows_bytes == 28896
    assert built.mlp_flops_per_sample == mlp_train_flops([682, 256, 128, 1])
    assert mlp_train_flops([682, 256, 128, 1]) == 6 * (682 * 256 + 256 * 128
                                                       + 128)


def test_dlrm_bytes_sgd_three_transfers():
    built, _ = _built("dlrm-mlperf")
    widths = [built.tables[t][1] for t in built.table_map]
    assert widths == [128] * 26 and built.hotness == [1] * 26
    assert roofline.embedding_bytes_per_sample(
        widths, built.hotness, "sgd") == 3 * 26 * 128 * 4 == 39936
    bottom = 6 * (13 * 512 + 512 * 256 + 256 * 128)
    top = 6 * (479 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256)
    assert built.mlp_flops_per_sample == bottom + top + 6 * 27 * 27 * 128


def test_least_step_says_which_bound():
    peaks = roofline.chip_peaks("TPU v5 lite")
    assert (peaks["hbm_gb_per_s"], peaks["bf16_tflop_per_s"],
            peaks["ici_gbit_per_s"]) == (819.0, 197.0, 1600.0)
    # Tiny V3: 65,536 samples x 28,896 B over 819 GB/s against 27 GFLOP x 3
    s, bound = roofline.least_step_s(peaks, 65536, 28896, 1246464)
    assert bound == "hbm" and s == pytest.approx(65536 * 28896 / 819e9)
    # DLRM share: 4,096 samples, 14.8 MFLOP each, against 39,936 B each
    s, bound = roofline.least_step_s(peaks, 4096, 39936, 14765376)
    assert bound == "mxu" and s == pytest.approx(4096 * 14765376 / 197e12)


def test_an_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="TPU v9"):
        roofline.chip_peaks("TPU v9")


def test_adam_nine_transfers_a_row():
    """Forward read, the backward's read and write, and the lazy update's
    read and write of the row and of each of its two moments."""
    assert roofline.embedding_bytes_per_sample(
        [2048], [1], "adam") == 9 * 2048 * 4
    with pytest.raises(KeyError):
        roofline.embedding_bytes_per_sample([8], [1], "lion")
