"""The Kimi-Linear configuration, its cell, its costs file, its reference
module and its seven readers: found by name with no edit to a file that
was there, held to ISSUE 51's hand figures of the cut (layers 1-8 of 27,
64 of 256 experts held), the reference held to the program's forward at
the tiny size with controls that fail, and the readers run on a synthetic
trace (operations as the chip's trace names them: PERF.md, PR 51)."""
import dataclasses
import importlib.util
import os
import types

import pytest

import costs
import kda
import launch_worker
import run as harness
from conftest import BENCH

CELL = "kimilinear.agent_turns"
READERS = {
    "kda.time_pct": ("recurrent state", "itl_p95_ms"),
    "kda.chunk_roofline_pct": ("recurrent state", "itl_p95_ms"),
    "kda.step_roofline_pct": ("recurrent state", "itl_p95_ms"),
    "held.time_pct": ("routed experts", "itl_p95_ms"),
    "held.mem_roofline_pct": ("routed experts", "itl_p95_ms"),
    "held.picks_pct": ("routed experts", "out_tok_s"),
    "hybrid.restore_hit_pct": ("KV pool", "itl_p95_ms"),
}


def test_the_cell_and_its_files_are_found_by_name():
    cell = harness.Cell(CELL)
    assert cell.config_name == "kimi-linear-48b-L8-e64" and cell.chips == 1
    assert cell.rate > 0
    assert cell.mix == harness.Cell("olmohybrid7b.agent_turns").mix   # unedited
    names = cell.metric_names("per_layer")
    assert set(READERS) <= set(names)
    for other in ("olmohybrid7b.agent_turns", "dsv2lite.shared_doc",
                  "laguna-xs2.agent_turns"):
        assert not set(READERS) & set(harness.Cell(other).metric_names("per_layer"))
    # the median first token is read per layer here (PERF.md section 2)
    assert set(cell.metric_names("end_to_end")) == {
        "itl_p95_ms", "out_tok_s", "setup_s"}
    assert {"gen.ttft_p50_ms", "gen.ttft_mean_ms"} <= set(names)
    entries = {m["name"]: m for m in cell.manifest["per_layer"]}
    for name, (layer, moves) in READERS.items():
        mod = cell.reader(name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.CELLS) == (
            name, "%", layer, moves, [CELL])
        assert entries[name]["workloads"] == [CELL]
    # every new entry stands behind every entry that was there
    listed = [m["name"] for m in cell.manifest["per_layer"]]
    at = listed.index("kda.time_pct")
    assert listed[at:at + 7] == list(READERS)
    assert cell.manifest["workloads"][-1]["name"] == CELL
    assert cell.manifest["configs"][-1]["name"] == cell.config_name


def test_the_configuration_reads_as_one_of_four_chips_that_share_each_layer():
    from gridllm_tpu.models.configs import get_config

    cell = harness.Cell(CELL)
    spec = cell.config
    cfg = launch_worker.model_config(spec, cell.config_name, False)
    assert (cfg.family, cfg.num_layers, cfg.linear_layers, cfg.cache_layers,
            cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
            cfg.num_experts, cfg.experts_per_token, cfg.held_experts,
            cfg.expert_width, cfg.kv_lora_rank, cfg.cache_dim,
            cfg.linear_num_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.linear_channel_decay,
            cfg.router_bias, cfg.routed_scaling_factor) == (
        "kimi_linear", 8, 6, 2, 2304, 9216, 163_840, 256, 8, (0, 64), 1024,
        512, 576, 32, 128, 128, True, True, 2.446)
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",) + (
        "linear_attention",) * 3 + ("full_attention",)
    assert cfg.cache_kinds == ("latent", "state")
    assert set(spec["reduced"]) == {
        "num_hidden_layers", "num_experts", "experts_held", "experts_first"}
    # the base is the share; with the cuts put back the file is the model
    share, whole = get_config(spec["base"]), get_config("kimi-linear:48b")
    assert dataclasses.replace(cfg, name=share.name, num_layers=27,
                               layer_types=share.layer_types) == share
    assert dataclasses.replace(share, name=whole.name, experts_held=None,
                               experts_first=None) == whole
    # a share that is not listed is refused, by the field's name
    unlisted = {**spec, "reduced": {k: v for k, v in spec["reduced"].items()
                                    if k != "experts_held"}}
    with pytest.raises(SystemExit, match="experts_held"):
        launch_worker.model_config(unlisted, "x", False)
    assert launch_worker.model_config(spec, "x", True).num_layers == 7
    assert spec["reference"]["margin_mean"] <= 0.02
    # every number of the catalog row's config stands under its key, but
    # the two the cut lists
    import json

    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):
        row = next(r for r in map(json.loads, open(guide))
                   if r["source_url"] == spec["source"])
        for key, value in row["config"].items():
            if key not in spec["reduced"]:
                assert spec[key] == value, key


def test_the_costs_file_holds_the_hand_figures():
    """ISSUE 51's arithmetic of the cut, in bf16."""
    spec = harness.Cell(CELL).config
    count = costs.of(spec)
    assert count is not costs and count.__file__.endswith("kimi_linear_costs.py")
    assert count.conv_channels(spec) == 12_288
    assert count.kda_mixer_params(spec) == 39_514_272
    assert count.mla_mixer_params(spec) == 29_114_880
    assert count.expert_params(spec) == 7_077_888
    assert count.expert_ffn_params(spec) == 460_652_800
    assert count.layer_params(spec, 0) == 103_219_872      # KDA, dense SwiGLU
    assert count.layer_params(spec, 1) == 500_171_680      # KDA, 64 experts
    assert count.layer_params(spec, 3) == 489_772_288      # MLA, 64 experts
    assert count.layer_counts(spec) == (1, 7)
    assert count.mixer_counts(spec) == (6, 2)
    assert count.embedding_params(spec) == 754_977_024
    assert count.total_params(spec) == 4_338_599_872
    assert round(count.weight_bytes(spec) / 1e9, 2) == 8.68
    whole = {**spec, "num_hidden_layers": 27, "num_experts": 256}
    assert count.total_params(whole) == 49_122_681_728     # the published 48B
    # two latent layers' rows as the model holds them: read low, never high
    assert count.kv_bytes_per_token(spec) == 2 * 576 * 2
    assert count.state_bytes_per_slot(spec) == 12_582_912 + 442_368
    assert count.step_weight_bytes(spec) == (
        4_338_599_872 - 754_977_024 + 163_840 * 2304) * 2
    # held bytes a launch: what its rows touch, at most every held expert
    assert count.held_expert_bytes(spec) == 7 * 64 * 7_077_888 * 2
    assert count.held_expert_bytes(spec, 100.0) == 100 * 7_077_888 * 2
    # the equations: 7 dk dv a token, head and KDA layer
    assert count.kda_chunk_flops(spec, 512) == 512 * 6 * 32 * 7.0 * 128 * 128
    # a live slot's state in and out and its rows' q, k, v and decay
    assert count.kda_step_bytes(spec, 8, 5) == 8 * 6 * (
        2 * 32 * 128 * 128 * 4 + 5 * 32 * (3 * 128 + 128) * 4)
    assert count.chip_share(spec) == {"weights": 1, "kv": 1, "heads": 1}
    assert count.chip_share({**spec, "mesh": "tp:2"}) is None


def _reference():
    spec = harness.Cell(CELL).config
    mod_spec = importlib.util.spec_from_file_location(
        "kimi_linear_f32_t", os.path.join(BENCH, spec["reference"]["module"]))
    ref = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(ref)
    return spec, ref


def test_the_reference_imports_nothing_from_the_program():
    spec, _ = _reference()
    with open(os.path.join(BENCH, spec["reference"]["module"])) as f:
        text = f.read()
    assert "import gridllm" not in text and "from gridllm" not in text


def test_the_reference_agrees_with_the_program_and_every_control_fails():
    """At the tiny size, in the configuration's own type's place float32:
    the program's forward reads the reference's logits; tokens the
    reference chose itself pass `check`, and fail it with a layer left
    out, the decay made one a head, left out, the convolution left out or
    the state carried in bfloat16."""
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
    assert (sizes["num_experts"], sizes["router_experts"],
            sizes["experts_first"]) == (4, 16, 4)
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
    limits = {"margin_abs": 0.003, "margin_rel": 0.0, "margin_mean": 0.0005}
    sound = reference_check.check(ref, params, sizes, cfg.vocab_size, limits,
                                  records)
    assert sound["agrees"] and sound["records"][0]["worst_shortfall"] < 1e-4
    skipped = reference_check.check(ref, params, sizes, cfg.vocab_size, limits,
                                    records, skip_layer=cfg.num_layers // 2)
    assert not skipped["agrees"]
    for word in ("scalar_decay", "no_decay", "no_conv", "state_dtype=bfloat16"):
        switch = reference_controls.parse_switch(word)[1]
        got = reference_check.check(
            reference_controls.Switched(ref, **switch), params, sizes,
            cfg.vocab_size, limits, records)
        assert not got["agrees"], (switch, got)


# -- the readers on a synthetic run -----------------------------------------

def _metrics(hit, short, miss, launches, padded, occupancy, picks, touched,
             verifies):
    m = 'model="kimi-linear-48b-L8-e64"'
    return "\n".join([
        f'gridllm_state_prefix_total{{{m},outcome="hit"}} {hit}',
        f'gridllm_state_prefix_total{{{m},outcome="short"}} {short}',
        f'gridllm_state_prefix_total{{{m},outcome="miss"}} {miss}',
        f'gridllm_engine_chunk_launches_total{{{m},width="512"}} {launches}',
        f'gridllm_engine_chunk_tokens_total{{{m},kind="padded"}} {padded}',
        f'gridllm_engine_batch_occupancy_bucket{{{m},le="+Inf"}} {occupancy[1]}',
        f'gridllm_engine_batch_occupancy_sum{{{m}}} {occupancy[0]}',
        f'gridllm_engine_batch_occupancy_count{{{m}}} {occupancy[1]}',
        f'gridllm_moe_picks_total{{{m},where="held"}} {picks[0]}',
        f'gridllm_moe_picks_total{{{m},where="absent"}} {picks[1]}',
        f'gridllm_moe_experts_touched_total{{{m}}} {touched}',
        f'gridllm_engine_phase_seconds_sum{{{m},phase="dispatch_verify"}} 1.0',
        f'gridllm_engine_phase_seconds_count{{{m},phase="dispatch_verify"}} {verifies}',
    ]) + "\n"


@pytest.fixture(scope="module")
def synthetic():
    def op(program, text, seconds):
        return {"program": program, "text": text, "seconds": seconds,
                "total_seconds": seconds, "count": 10}

    v, mx = "jit_verify_block_fn", "jit_mixed_chunk_fn"
    ops = {
        "mixed/kda_chunk": op(
            mx, "%kda_chunk.3 = (f32[8,64,4096]{2,1,0}, f32[128,4096]{1,0}) custom-call(", 0.020),
        "mixed/kda_step": op(
            mx, "%kda_step.5 = (f32[6,16,128,4096]{3,2,1,0}) custom-call(", 0.004),
        "verify/kda_step": op(
            v, "%kda_step.7 = (f32[6,16,128,4096]{3,2,1,0}, f32[16,8,4096]) custom-call(", 0.060),
        "verify/conv": op(
            v, "%fusion.12 = f32[16,5,12288]{2,1,0} fusion(bf16[16,8,12288]", 0.010),
        "verify/pairs": op(
            v, "%fusion.118 = (f32[16,32,8,8]{3,2,1,0}, f32[16,32,8,8]) fusion(f32[16,32,128,8]", 0.006),
        "verify/rows": op(
            v, "%copy.203 = f32[16,2,32,8,128]{4,3,2,1,0} copy(f32[16,2,32,8,128]{4,2,0,3,1}", 0.004),
        "verify/l2norm": op(
            v, "%fusion.40 = f32[16,5,32,128]{3,2,1,0} fusion(f32[16,5,12288]", 0.005),
        # the latent layers have 32 heads of 128 too: told apart by the
        # latent's widths, and left out
        "verify/mla_absorb": op(
            v, "%fusion.50 = f32[16,5,32,512]{3,2,1,0} fusion(bf16[16,5,32,128], bf16[512,32,128]", 0.012),
        "verify/ragged": op(
            v, "%ragged_attention.2 = bf16[16,1,160,512]{3,2,1,0} custom-call(", 0.050),
        # the layer's projections: shapes of the mixer, but plain products
        "verify/w_v": op(
            v, "%fusion.1131 = bf16[16,5,4096]{2,0,1} fusion(bf16[16,5,2304]{2,0,1}, bf16[2304,4096]", 0.030),
        # the held experts, all-experts form in a verify launch
        "verify/experts_up": op(
            v, "%fusion.70 = bf16[16,5,64,1024]{3,2,1,0} fusion(bf16[16,5,2304], bf16[64,2304,1024]", 0.200),
        "verify/experts_down": op(
            v, "%fusion.71 = bf16[16,5,2304]{2,1,0} fusion(bf16[16,5,64,1024], bf16[64,1024,2304]", 0.100),
        # and the sorted form in a mixed launch
        "mixed/ragged_dot": op(
            mx, "%ragged-dot.4 = bf16[4224,1024]{1,0} custom-call(bf16[4224,2304]", 0.040),
        "mixed/pairs": op(
            mx, "%fusion.1682 = f32[8,32,16,16,128]{4,3,2,1,0} fusion(f32[8,32,64,128]", 0.030),
        "mixed/layout": op(
            mx, "%copy.4832 = f32[8,64,32,128]{3,2,1,0} copy(f32[8,64,32,128]{3,1,2,0}", 0.010),
        "mixed/conv": op(
            mx, "%divide_multiply_fusion.12 = f32[512,12288]{0,1} fusion(f32[515,12288]", 0.005),
    }
    return {
        "config": harness.Cell(CELL).config,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "requests": [types.SimpleNamespace(group=i // 10) for i in range(40)],
        "trace": {
            "devices": {"/device:TPU:0": {"busy_s": 1.0, "idle_pct": 50.0}},
            "programs": {v: {"seconds": 0.8, "count": 40},
                         mx: {"seconds": 0.2, "count": 10}},
            "ops": ops},
        "worker_before": _metrics(0, 0, 0, 0, 0, (0, 0), (0, 0), 0, 0),
        "worker_after": _metrics(33, 2, 1, 50, 50 * 512, (700, 100),
                                 (2600, 7400), 9000, 300),
        "trace_counters": (
            _metrics(0, 0, 0, 0, 0, (100, 20), (0, 0), 1000, 20),
            _metrics(0, 0, 0, 0, 0, (420, 60), (0, 0), 5000, 60)),
        "samples": [],
    }


def test_the_readers_on_a_synthetic_trace(synthetic):
    cell = harness.Cell(CELL)
    got = {name: cell.reader(name).compute(synthetic) for name in READERS}
    spec, count = synthetic["config"], costs.of(synthetic["config"])
    peaks = costs.peaks("TPU v5 lite")
    # both kernels, the convolutions, the pair terms, the rows' copies and
    # the norms; not the latent layers' operations with the same heads,
    # not the ragged kernel, no projection, no expert
    assert got["kda.time_pct"] == pytest.approx(100.0 * (
        0.020 + 0.004 + 0.060 + 0.010 + 0.006 + 0.004 + 0.005
        + 0.030 + 0.010 + 0.005))
    assert {o["key"] for o in kda.chunk_rule_ops(synthetic)} == {
        "mixed/kda_chunk", "mixed/pairs", "mixed/layout"}
    assert got["held.time_pct"] == pytest.approx(100.0 * (0.200 + 0.100 + 0.040))
    assert got["held.picks_pct"] == pytest.approx(26.0)
    assert got["hybrid.restore_hit_pct"] == pytest.approx(100.0 * 33 / 36)
    # 512 padded rows a launch over the chunked rule's 6 ms a launch
    assert got["kda.chunk_roofline_pct"] == pytest.approx(
        100.0 * count.kda_chunk_flops(spec, 512) / peaks["bf16_flops_per_s"]
        / ((0.020 + 0.030 + 0.010) / 10))
    # 8 live slots a launch over the capture, K + 1 = 5 rows, 1.5 ms
    assert got["kda.step_roofline_pct"] == pytest.approx(
        100.0 * count.kda_step_bytes(spec, 8.0, 5) / peaks["hbm_bytes_per_s"]
        / (0.060 / 40))
    # 4,000 held experts touched over the capture's 40 launches: 100 a
    # launch of the 448 held, over the 7.5 ms a launch their products take
    assert got["held.mem_roofline_pct"] == pytest.approx(
        100.0 * count.held_expert_bytes(spec, 100.0) / peaks["hbm_bytes_per_s"]
        / (0.300 / 40))
    for name in ("kda.chunk_roofline_pct", "kda.step_roofline_pct",
                 "held.mem_roofline_pct"):
        assert 0 < got[name] < 100


def test_a_program_without_the_kernels_or_counters_reads_as_nothing(synthetic):
    """The parent's trace and scrape, or another family's configuration:
    every reader returns None and none raises."""
    cell = harness.Cell(CELL)
    other = {**synthetic, "config": harness.Cell("olmohybrid7b.agent_turns").config}
    bare = {**synthetic, "trace": {}, "worker_before": "", "worker_after": "",
            "trace_counters": None, "samples": []}
    for name in READERS:
        assert cell.reader(name).compute(bare) is None, name
        if name != "held.picks_pct":
            assert cell.reader(name).compute(other) is None, name
