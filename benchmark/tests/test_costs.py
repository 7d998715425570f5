"""costs.py against the hand figures of ISSUE 23."""
import json
import os

import pytest

import costs
from conftest import BENCH


@pytest.fixture
def spec():
    with open(os.path.join(BENCH, "configs", "mistral-7b-v0.3-L20.json")) as f:
        return json.load(f)


def test_hand_figures(spec):
    assert costs.layer_params(spec) / 1e6 == pytest.approx(218.1, abs=0.05)
    assert costs.embedding_params(spec) / 1e6 == pytest.approx(268.4, abs=0.05)
    assert costs.total_params(spec) / 1e9 == pytest.approx(4.63, abs=0.005)
    assert costs.weight_bytes(spec) / 1e9 == pytest.approx(9.26, abs=0.005)
    assert costs.kv_bytes_per_token(spec) == 80 * 1024
    full = dict(spec, num_hidden_layers=32)
    assert costs.weight_bytes(full) / 1e9 == pytest.approx(14.5, abs=0.05)


def test_flash_flops(spec):
    # 32 heads x 512 x 512 x 128 x (QK + PV) x 2 FLOP, half for causality
    assert costs.flash_prefill_flops(spec, 512) == 32 * 512 * 512 * 128 * 2 * 2 / 2
    assert costs.flash_prefill_flops(spec, 1024) == 4 * costs.flash_prefill_flops(spec, 512)


def test_unknown_device_is_an_error():
    assert costs.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("TPU v9")
    with pytest.raises(KeyError):
        costs.peaks("_source")
