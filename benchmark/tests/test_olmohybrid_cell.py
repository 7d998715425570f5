"""The Olmo-Hybrid configuration, its cell, its costs file, its reference
module and its five readers: found by name with no edit to a file that
was there, held to ISSUE 42's hand figures, the reference held to the
program's forward at the tiny size with a control that fails, and the
readers run on a synthetic trace (operations as the chip's trace names
them: PERF.md, PR 42)."""
import dataclasses
import importlib.util
import os
import types

import pytest

import costs
import gdn
import launch_worker
import run as harness
from conftest import BENCH

CELL = "olmohybrid7b.agent_turns"
READERS = {"gdn.time_pct": "itl_p95_ms", "gdn.chunk_roofline_pct": "ttft_p50_ms",
           "gdn.step_roofline_pct": "itl_p95_ms", "state.hit_pct": "ttft_p50_ms",
           "state.snapshot_pool_peak_pct": "out_tok_s"}


def test_the_cell_and_its_files_are_found_by_name():
    cell = harness.Cell(CELL)
    assert cell.config_name == "olmo-hybrid-7b-L20" and cell.chips == 1
    assert cell.rate > 0
    stream, = cell.mix["streams"]
    assert stream["group_offsets_s"] == [0, 4, 5, 6, 7, 8, 9, 10, 11, 12]
    assert stream["shared_tokens"] == harness.Cell(
        "mistral7b.shared_doc").mix["streams"][0]["shared_tokens"]
    assert (stream["own_tokens"]["value"], stream["output_tokens"]["value"]) == (64, 48)
    names = cell.metric_names("per_layer")
    assert set(READERS) <= set(names)
    for other in ("mistral7b.shared_doc", "dsv2lite.shared_doc"):
        assert not set(READERS) & set(harness.Cell(other).metric_names("per_layer"))
    assert set(cell.metric_names("end_to_end")) == {
        "ttft_p50_ms", "itl_p95_ms", "out_tok_s", "setup_s"}
    entries = {m["name"]: m for m in cell.manifest["per_layer"]}
    for name, moves in READERS.items():
        mod = cell.reader(name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.CELLS) == (
            name, "%", "recurrent state", moves, [CELL])
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["layer"] == "recurrent state"
    spec = cell.config
    cfg = launch_worker.model_config(spec, cell.config_name, False)
    assert (cfg.family, cfg.num_layers, cfg.linear_layers, cfg.cache_layers,
            cfg.hidden_size, cfg.intermediate_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim_, cfg.vocab_size,
            cfg.linear_num_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.linear_conv_kernel,
            cfg.linear_allow_neg_eigval, cfg.rope_theta) == (
        "olmo_hybrid", 20, 15, 5, 3840, 11_008, 30, 30, 128, 100_352, 30, 96,
        192, 4, True, 0.0)
    assert list(spec["reduced"]) == ["num_hidden_layers"]
    from gridllm_tpu.models.configs import get_config

    whole = get_config(spec["base"])
    assert dataclasses.replace(
        cfg, name=whole.name, num_layers=32,
        layer_types=whole.layer_types) == whole
    assert spec["reference"]["margin_mean"] <= 0.02


def test_the_costs_file_holds_the_hand_figures():
    """ISSUE 42's arithmetic, in bf16."""
    spec = harness.Cell(CELL).config
    count = costs.of(spec)
    assert count is not costs and count.__file__.endswith("olmo_hybrid_costs.py")
    assert count.conv_channels(spec) == 11_520
    assert count.linear_layer_params(spec) == 215_570_172
    assert count.full_layer_params(spec) == 185_809_920
    assert count.layer_counts(spec) == (15, 5)
    assert count.embedding_params(spec) == 770_707_200
    assert count.total_params(spec) == 4_933_309_380
    assert round(count.weight_bytes(spec) / 1e9, 2) == 9.87
    whole = {**spec, "num_hidden_layers": 32}
    assert count.total_params(whole) == 7_430_870_688
    assert count.kv_bytes_per_token(spec) == 76_800
    assert count.state_bytes_per_slot(spec) == 33_177_600 + 1_036_800
    assert count.step_weight_bytes(spec) == (
        15 * 215_570_172 + 5 * 185_809_920 + 100_352 * 3840) * 2
    # the equations: 7 dk dv a token, head and linear layer
    assert count.gdn_chunk_flops(spec, 1024) == 1024 * 15 * 30 * 7.0 * 96 * 192
    # a live slot's state in and out and its rows' q, k, v, every layer
    assert count.gdn_step_bytes(spec, 8, 5) == 8 * 15 * (
        2 * 30 * 96 * 192 * 4 + 5 * 30 * (96 + 96 + 192) * 4)
    assert count.chip_share(spec) == {"weights": 1, "kv": 1, "heads": 1}
    assert count.chip_share({**spec, "mesh": "tp:2"}) is None


def _reference():
    spec = harness.Cell(CELL).config
    mod_spec = importlib.util.spec_from_file_location(
        "olmo_hybrid_f32_t", os.path.join(BENCH, spec["reference"]["module"]))
    ref = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(ref)
    return spec, ref


def test_the_reference_imports_nothing_from_the_program():
    spec, _ = _reference()
    with open(os.path.join(BENCH, spec["reference"]["module"])) as f:
        text = f.read()
    assert "import gridllm" not in text and "from gridllm" not in text


def test_the_reference_agrees_with_the_program_and_a_control_fails():
    """At the tiny size, in the configuration's own type's place float32:
    the program's forward reads the reference's logits; tokens the
    reference chose itself pass `check`, and fail it with a layer left
    out, beta not doubled, no decay, no convolution, or RoPE put on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import loadgen
    import reference_check
    import reference_controls
    from gridllm_tpu.engine.engine import _model_module
    from gridllm_tpu.models.configs import get_config

    spec, ref = _reference()
    cfg = get_config(spec["rehearse_base"])
    mod = _model_module(cfg)
    params = mod.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    sizes = reference_check.reference_sizes(ref, cfg, spec, rehearse=True)
    assert sizes["linear_key_head_dim"] == 16 and sizes["linear_allow_neg_eigval"]
    seq = [int(t) for t in jax.random.randint(jax.random.PRNGKey(7), (80,), 0, 256)]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(mod.forward(params, cfg, jnp.asarray(seq)[None]))[0]
    assert np.abs(got - ref.logits(params, sizes, seq)).max() < 1e-4
    for _ in range(16):           # greedy under the penalty the benchmark asks for
        row = ref.logits(params, sizes, seq)[-1:]
        row = ref.penalized(jnp.asarray(row), seq, len(seq),
                            loadgen.REPEAT_PENALTY, loadgen.REPEAT_LAST_N)
        seq.append(int(row[0].argmax()))
    records = [{"index": 0, "context": seq, "n_prompt": 80}]
    limits = {"margin_abs": 0.01, "margin_rel": 0.0, "margin_mean": 0.002}
    sound = reference_check.check(ref, params, sizes, cfg.vocab_size, limits,
                                  records)
    assert sound["agrees"] and sound["records"][0]["worst_shortfall"] < 1e-4
    skipped = reference_check.check(ref, params, sizes, cfg.vocab_size, limits,
                                    records, skip_layer=cfg.num_layers // 2)
    assert not skipped["agrees"]
    for word in ("beta_single", "no_decay", "no_conv", "rope_theta=500000"):
        switch = reference_controls.parse_switch(word)[1]
        got = reference_check.check(
            reference_controls.Switched(ref, **switch), params, sizes,
            cfg.vocab_size, limits, records)
        assert not got["agrees"], (switch, got)


# -- the readers on a synthetic run -----------------------------------------

def _metrics(hit, short, miss, launches, padded, occupancy, used):
    m = 'model="olmo-hybrid-7b-L20"'
    return "\n".join([
        f'gridllm_state_prefix_total{{{m},outcome="hit"}} {hit}',
        f'gridllm_state_prefix_total{{{m},outcome="short"}} {short}',
        f'gridllm_state_prefix_total{{{m},outcome="miss"}} {miss}',
        f'gridllm_engine_chunk_launches_total{{{m},width="256"}} {launches}',
        f'gridllm_engine_chunk_tokens_total{{{m},kind="padded"}} {padded}',
        f'gridllm_engine_batch_occupancy_bucket{{{m},le="+Inf"}} {occupancy[1]}',
        f'gridllm_engine_batch_occupancy_sum{{{m}}} {occupancy[0]}',
        f'gridllm_engine_batch_occupancy_count{{{m}}} {occupancy[1]}',
        f'gridllm_state_snapshot_pool_used{{{m}}} {used}',
        f'gridllm_state_snapshot_pool_capacity{{{m}}} 40',
    ]) + "\n"


@pytest.fixture(scope="module")
def synthetic():
    def op(program, text, seconds):
        return {"program": program, "text": text, "seconds": seconds,
                "total_seconds": seconds, "count": 10}

    ops = {
        "mixed/gdn_chunk": op(
            "jit_mixed_chunk_fn",
            "%gdn_chunk.3 = (f32[4,64,5760]{2,1,0}, f32[96,5760]{1,0}) custom-call(", 0.020),
        "mixed/gdn_step": op(
            "jit_mixed_chunk_fn",
            "%gdn_step.5 = (f32[15,16,96,5760]{3,2,1,0}) custom-call(", 0.004),
        "verify/gdn_step": op(
            "jit_verify_block_fn",
            "%gdn_step.9 = (f32[15,16,96,5760]{3,2,1,0}, f32[16,8,5760]) custom-call(", 0.090),
        "verify/conv": op(
            "jit_verify_block_fn",
            "%fusion.12 = f32[16,5,11520]{2,1,0} fusion(bf16[16,8,11520]", 0.010),
        "verify/gate": op(
            "jit_verify_block_fn",
            "%fusion.40 = bf16[16,5,30,192]{3,2,1,0} fusion(f32[16,5,30,192]", 0.006),
        "verify/ragged": op(
            "jit_verify_block_fn",
            "%ragged_attention.2 = bf16[16,30,5,128]{3,2,1,0} custom-call(", 0.050),
        "verify/mlp": op(
            "jit_verify_block_fn",
            "%fusion.77 = bf16[80,11008]{1,0} fusion(bf16[80,3840]", 0.400),
        # the layer's projections and a weight's copy, as the chip's trace
        # has them (PR 42, call 6): shapes of the state, but products
        "verify/w_v": op(
            "jit_verify_block_fn",
            "%fusion.1131 = bf16[16,5,5760]{2,0,1} fusion(bf16[16,5,3840]{2,0,1} "
            "%fusion.1128, bf16[5,3840,5760]{2,1,0} %get-tuple-element.4170", 0.036),
        "verify/w_v_heads": op(
            "jit_verify_block_fn",
            "%fusion.1117 = bf16[16,5,30,192]{3,0,2,1} fusion("
            "bf16[30,192,3840,1]{2,1,0,3} %bitcast.1846, bf16[16,5,3840]", 0.042),
        "verify/w_o": op(
            "jit_verify_block_fn",
            "%fusion.1121 = (f32[16,5]{0,1}, bf16[16,5,3840]{2,0,1}) fusion("
            "bf16[16,5,5760]{2,0,1} %reshape.2559, bf16[5,5760,3840]", 0.036),
        "verify/weight_copy": op(
            "jit_verify_block_fn",
            "%copy.2035 = bf16[1,3840,5760]{1,2,0} copy(bf16[1,3840,5760]{2,1,0}", 0.040),
        # the chunked rule outside its kernel: a block's system, a layout copy
        "mixed/solve": op(
            "jit_mixed_chunk_fn",
            "%fusion.1682 = f32[16,30,64,64]{3,2,1,0} fusion(f32[16,64,30,96]", 0.030),
        "mixed/layout": op(
            "jit_mixed_chunk_fn",
            "%copy.4832 = f32[16,64,30,192]{3,2,1,0} copy(f32[16,64,30,192]{3,1,2,0}", 0.010),
        "mixed/conv": op(
            "jit_mixed_chunk_fn",
            "%divide_multiply_fusion.12 = f32[1024,11520]{0,1} fusion(f32[1027,11520]", 0.005),
    }
    return {
        "config": harness.Cell(CELL).config,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "requests": [types.SimpleNamespace(group=i // 10) for i in range(40)],
        "trace": {
            "devices": {"/device:TPU:0": {"busy_s": 1.0, "idle_pct": 50.0}},
            "programs": {"jit_verify_block_fn": {"seconds": 0.8, "count": 40},
                         "jit_mixed_chunk_fn": {"seconds": 0.2, "count": 10}},
            "ops": ops},
        "worker_before": _metrics(0, 0, 0, 0, 0, (0, 0), 0),
        "worker_after": _metrics(33, 2, 1, 50, 50 * 512, (700, 100), 30),
        "trace_counters": (_metrics(0, 0, 0, 0, 0, (100, 20), 0),
                           _metrics(0, 0, 0, 0, 0, (420, 60), 0)),
        "samples": [(0.0, _metrics(0, 0, 0, 0, 0, (0, 0), 12)),
                    (0.5, _metrics(0, 0, 0, 0, 0, (0, 0), 30))],
    }


def test_the_readers_on_a_synthetic_trace(synthetic):
    cell = harness.Cell(CELL)
    got = {name: cell.reader(name).compute(synthetic) for name in READERS}
    spec, count = synthetic["config"], costs.of(synthetic["config"])
    peaks = costs.peaks("TPU v5 lite")
    # both kernels, the convolutions, the gate, the blocks' systems and the
    # layout copies; not attention, not the MLP, and none of the layer's
    # projections or weight copies, whatever shape of the state they carry
    assert got["gdn.time_pct"] == pytest.approx(
        100.0 * (0.020 + 0.004 + 0.090 + 0.010 + 0.006 + 0.030 + 0.010 + 0.005))
    assert {o["key"] for o in gdn.chunk_rule_ops(synthetic)} == {
        "mixed/gdn_chunk", "mixed/solve", "mixed/layout"}
    assert got["state.hit_pct"] == pytest.approx(100.0 * 33 / 36)
    assert got["state.snapshot_pool_peak_pct"] == pytest.approx(75.0)
    # 512 padded rows a launch over the chunked rule's 6 ms a launch: the
    # kernel's 2 and the 4 that XLA runs around it
    assert got["gdn.chunk_roofline_pct"] == pytest.approx(
        100.0 * count.gdn_chunk_flops(spec, 512) / peaks["bf16_flops_per_s"]
        / ((0.020 + 0.030 + 0.010) / 10))
    # 8 live slots a launch over the capture, K + 1 = 5 rows, 2.25 ms
    assert got["gdn.step_roofline_pct"] == pytest.approx(
        100.0 * count.gdn_step_bytes(spec, 8.0, 5) / peaks["hbm_bytes_per_s"]
        / (0.090 / 40))
    for name in ("gdn.chunk_roofline_pct", "gdn.step_roofline_pct"):
        assert 0 < got[name] < 100


def test_a_program_without_the_kernels_or_counters_reads_as_nothing(synthetic):
    """The parent's trace and scrape, or another family's configuration:
    every reader returns None and none raises."""
    cell = harness.Cell(CELL)
    dense = {**synthetic, "config": harness.Cell("mistral7b.shared_doc").config}
    bare = {**synthetic, "trace": {}, "worker_before": "", "worker_after": "",
            "trace_counters": None, "samples": []}
    for name in READERS:
        assert cell.reader(name).compute(bare) is None, name
        if name.startswith("gdn."):
            assert cell.reader(name).compute(dense) is None, name
