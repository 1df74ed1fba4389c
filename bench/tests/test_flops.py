"""Model FLOPs per token and the peaks table, against hand counts."""
import json

import pytest

from bench import harness
from bench.tests.conftest import FAMILY_KEYS, legacy, tiny_config


def conf(name):
    return harness.load_config(name)


def test_qwen_4l_matmul_params_by_hand():
    per_layer = (2048 * 16 * 128          # wq
                 + 2 * 2048 * 2 * 128     # wk, wv
                 + 16 * 128 * 2048        # wo
                 + 3 * 2048 * 11008)      # w_in, w_gate, w_out
    assert per_layer == 77_070_336
    head = 2048 * 151_936
    c = conf("qwen2.5-3b-4l")
    assert harness.family(c).matmul_params(c) == \
        4 * per_layer + head == 619_446_272


@pytest.mark.parametrize("name,seq,want", [
    ("qwen2.5-3b-4l", 1024, 6 * 619_446_272 + 12 * 4 * 16 * 128 * 1024),
    ("qwen2.5-3b-4l", 4096, 6 * 619_446_272 + 12 * 4 * 16 * 128 * 4096),
])
def test_flops_per_token(name, seq, want):
    c = conf(name)
    assert harness.family(c).flops_per_token(c, seq) == want


@pytest.mark.parametrize("seq", [1024, 4096])
def test_dense_flops_are_the_dense_only_count_bit_for_bit(seq):
    c = conf("qwen2.5-3b-4l")
    got = harness.family(c).flops_per_token(c, seq)
    assert got == legacy().flops_per_token(c, seq)
    assert got.hex() == legacy().flops_per_token(c, seq).hex()


# tiny test families: hidden 64, 4 query heads and 2 kv heads of 16,
# q/k/v bias, vocabulary 500
ATTN = 64 * 4 * 16 + 2 * 64 * 2 * 16 + 4 * 16 * 64      # 12288
HEAD = 64 * 500


@pytest.mark.parametrize("family,seq,want", [
    # 4 layers of ff 128; layers 0 and 2 attend to 8 keys, 1 and 3 to all
    ("dense_window", 64,
     6 * (4 * (ATTN + 3 * 64 * 128) + HEAD) + 12 * 4 * 16 * (8 + 64) * 2),
    ("dense_window", 4,
     6 * (4 * (ATTN + 3 * 64 * 128) + HEAD) + 12 * 4 * 16 * 4 * 4),
    # one dense layer of ff 128; two expert layers, each a router to 8
    # experts, 2 routed and 1 shared expert of ff 32 used per token
    ("moe", 64,
     6 * (ATTN + 3 * 64 * 128 + 2 * (ATTN + 64 * 8 + 3 * 3 * 64 * 32)
          + HEAD) + 12 * 3 * 4 * 16 * 64),
])
def test_test_family_flops_by_hand(tiny_bench, family, seq, want):
    c = tiny_config(**FAMILY_KEYS[family])
    assert harness.family(c).flops_per_token(c, seq) == want


def test_peaks_table_has_v5e_with_its_source():
    table = json.loads((harness.BENCH / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    assert harness.load_peaks("TPU v5 lite") == {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.load_peaks("TPU v9 imaginary")
