"""The ``gpt2`` family's FLOP and bytes functions against values worked by hand."""

import json

import pytest

from perfbench import costs, manifest, peaks
from tests.perfbench import tiny

gpt2 = tiny.gpt2_family()
CONFIGS = manifest.ROOT / "perfbench" / "configs"


def sizes(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_matmul_params_by_hand():
    # small: 12 * (768*2304 + 768*768 + 2 * 768*3072) = 84,934,656; head 50257*768 = 38,597,376
    assert gpt2.matmul_params(sizes("gpt2-small-lm-train")) == 84_934_656 + 38_597_376
    # medium: 24 * 12 * 1024**2 = 301,989,888; head 50257*1024 = 51,463,168
    assert gpt2.matmul_params(sizes("gpt2-medium-serve")) == 301_989_888 + 51_463_168


def test_decode_flops_by_hand():
    medium = sizes("gpt2-medium-serve")
    # one row over 100 keys: 2 * 353,453,056 dense + 24 layers * 4 * 100 * 1024 attention
    assert gpt2.decode_flops(medium, [100]) == 2 * 353_453_056 + 24 * 4 * 100 * 1024
    assert gpt2.decode_flops(medium, [100, 100]) == 2 * gpt2.decode_flops(medium, [100])


def test_decode_attention_bytes_by_hand():
    medium = sizes("gpt2-medium-serve")
    # a key's K and V over all heads: 2 * 1024 * 2 B = 4096 B a layer, 98,304 B over 24 layers
    per_key = 24 * 2 * 1024 * 2
    per_row = 24 * 2 * 1024 * 2  # query in, output out
    assert gpt2.decode_attention_bytes(medium, [100], 2, 2) == 100 * per_key + per_row
    assert per_key == 98_304


def test_train_flops_by_hand():
    small = sizes("gpt2-small-lm-train")
    # a 4-token document: keys seen 1, 2, 3, 4 -> 2.5 on average
    assert costs.mean_causal_keys([4]) == 2.5
    assert costs.mean_causal_keys([4, 2]) == pytest.approx((10 + 3) / 6)
    # full rows of one document: (1024 + 1) / 2 keys on average
    per_token = gpt2.train_flops_per_token(small, 512.5)
    assert per_token == 6 * 123_532_032 + 3 * 12 * 4 * 512.5 * 768
    assert 0.79e9 < per_token < 0.80e9


def test_peaks_are_a_table_and_an_unknown_device_is_an_error():
    v5e = peaks.for_device("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.for_device("cpu")
