"""``flops.py`` against hand counts for GPT-2 small."""
import json
from pathlib import Path

import pytest

import flops

SMALL = json.loads((Path(flops.__file__).parent / "configs"
                    / "gpt2-small.json").read_text())


def test_parameter_counts_match_the_published_model():
    # 124,439,808: the count PR 21 read off the chip for GPTConfig()
    assert flops.n_params(SMALL) == 124_439_808
    # 12 layers x 12 x 768^2 + the 50257 x 768 tied head
    assert flops.matmul_params(SMALL) == 84_934_656 + 38_597_376


def test_train_flops_are_six_n_plus_causal_attention():
    per_token = flops.train_flops_per_token(SMALL, 1024)
    six_n = 6 * 123_532_032
    attention = 3 * 4 * 12 * 768 * 512      # fwd+bwd, mean context S/2
    assert per_token == six_n + attention
    assert attention / six_n == pytest.approx(0.0764, abs=1e-3)


def test_decode_bytes_are_weights_plus_live_tokens_not_the_pool():
    live = 32 * 300
    need = flops.decode_step_bytes(SMALL, live)
    assert need == 124_439_808 * 2 + live * 2 * 12 * 768 * 2
    # the pool's size appears nowhere: the same live tokens in a pool
    # four times as large need the same bytes
    assert flops.kv_bytes_per_token(SMALL) == 36_864


def test_roofline_takes_the_larger_bound_and_unknown_devices_fail():
    peaks = flops.peaks_of("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert flops.roofline_seconds(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert flops.roofline_seconds(1.0, 819e9, peaks) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        flops.peaks_of("TPU v9 imaginary")
