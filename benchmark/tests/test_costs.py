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


def test_one_chip_counts_are_what_they_were(spec):
    """``chips: 1``, ``mesh: ""``: the numbers every reader had before a
    chip's share existed, to the last digit, and a share of 1 each."""
    assert (spec["chips"], spec["mesh"]) == (1, "")
    assert costs.of(spec) is costs
    assert costs.total_params(spec) == 4_630_679_552
    assert costs.weight_bytes(spec) == 9_261_359_104
    assert costs.step_weight_bytes(spec) == 8_992_915_456
    assert costs.kv_bytes_per_token(spec) == 81_920
    assert costs.flash_prefill_flops(spec, 512) == 2_147_483_648.0
    assert costs.chip_share(spec) == {"weights": 1, "kv": 1, "heads": 1}
    assert costs.mesh_axes(spec) == {} and costs.mesh_size(spec) == 1


def test_tp_divides_weights_kv_and_heads(spec):
    tp4 = dict(spec, mesh="tp:4", chips=4)
    assert costs.mesh_axes(tp4) == {"tp": 4} and costs.mesh_size(tp4) == 4
    assert costs.chip_share(tp4) == {"weights": 4, "kv": 4, "heads": 4}
    # the whole-model counts do not move with the mesh
    assert costs.step_weight_bytes(tp4) == costs.step_weight_bytes(spec)
    assert costs.chip_share(dict(spec, mesh="dp:1,tp:2")) == {
        "weights": 2, "kv": 2, "heads": 2}


def test_no_rule_is_none_and_a_split_head_is_refused(spec):
    assert costs.chip_share(dict(spec, mesh="ep:4")) is None
    assert costs.chip_share(dict(spec, mesh="pp:2,tp:2")) is None
    with pytest.raises(ValueError, match="KV heads"):
        costs.chip_share(dict(spec, mesh="tp:3"))
    with pytest.raises(ValueError, match="KV heads"):
        costs.chip_share(dict(spec, num_key_value_heads=2, mesh="tp:4"))
    for bad in ("tp", "tp:x", "zz:4", "tp:0"):
        with pytest.raises(ValueError, match="mesh"):
            costs.mesh_axes({"mesh": bad})


def test_a_configuration_names_its_own_costs(spec, tmp_path, monkeypatch):
    (tmp_path / "family_costs").mkdir()
    (tmp_path / "family_costs" / "half.py").write_text(
        "import costs\n\n"
        "def step_weight_bytes(spec):\n"
        "    return costs.step_weight_bytes(spec) // 2\n\n"
        "def chip_share(spec):\n    return {'weights': 1, 'kv': 1, 'heads': 1}\n")
    monkeypatch.setattr(costs, "HERE", str(tmp_path))
    mine = costs.of(dict(spec, costs="family_costs/half.py"))
    assert mine is not costs and mine is costs.of(dict(spec, costs="family_costs/half.py"))
    assert mine.step_weight_bytes(spec) * 2 == costs.step_weight_bytes(spec)
    with pytest.raises(ValueError, match="not under"):
        costs.of(dict(spec, costs="../gridllm_tpu/x.py"))
