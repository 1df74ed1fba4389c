"""Model FLOPs per token and the peaks table, against hand counts."""
import json

import pytest

from bench import flops, harness


def conf(name):
    return harness.load_config(name)


def test_qwen_4l_matmul_params_by_hand():
    per_layer = (2048 * 16 * 128          # wq
                 + 2 * 2048 * 2 * 128     # wk, wv
                 + 16 * 128 * 2048        # wo
                 + 3 * 2048 * 11008)      # w_in, w_gate, w_out
    assert per_layer == 77_070_336
    head = 2048 * 151_936
    assert flops.matmul_params(conf("qwen2.5-3b-4l")) == \
        4 * per_layer + head == 619_446_272


@pytest.mark.parametrize("name,seq,want", [
    ("qwen2.5-3b-4l", 1024, 6 * 619_446_272 + 12 * 4 * 16 * 128 * 1024),
    ("qwen2.5-3b-4l", 4096, 6 * 619_446_272 + 12 * 4 * 16 * 128 * 4096),
])
def test_flops_per_token(name, seq, want):
    assert flops.flops_per_token(conf(name), seq) == want


def test_peaks_table_has_v5e_with_its_source():
    table = json.loads((harness.BENCH / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    assert harness.load_peaks("TPU v5 lite") == {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.load_peaks("TPU v9 imaginary")
