"""The LongCat-Flash configuration, its cell, its costs file, its reference
module and its eight readers: found by name with no edit to a file that
was there, held to ISSUE 57's hand figures of the cut (blocks 0-3 of 28,
16 of 512 routed experts held, an eighth of the vocabulary held), the reference
held to the program's forward at the tiny size with controls that fail,
and the readers run on a synthetic trace (operations as the chip's trace
names them: PERF.md, PR 57)."""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

import costs
import launch_worker
import lcf
import run as harness
from conftest import BENCH, ROOT

CELL = "longcat.long_doc"
READERS = {
    "lcf.mla_time_pct": ("latent attention", "device_trace"),
    "lcf.mla_chunk_roofline_pct": ("latent attention", "device_trace"),
    "lcf.mla_decode_roofline_pct": ("latent attention", "device_trace"),
    "lcf.qlora_time_pct": ("latent attention", "device_trace"),
    "lcf.held_time_pct": ("routed experts", "device_trace"),
    "lcf.held_mem_roofline_pct": ("routed experts", "device_trace"),
    "lcf.zero_picks_pct": ("routed experts", "program_counter"),
    "lcf.held_picks_pct": ("routed experts", "program_counter"),
}


def test_the_cell_and_its_files_are_found_by_name():
    cell = harness.Cell(CELL)
    assert cell.config_name == "longcat-flash-omni-L4-e16" and cell.chips == 1
    assert cell.rate > 0
    assert cell.mix == harness.Cell("smallthinker21b.long_doc").mix   # unedited
    assert cell.params["reference"] == {"max_prompt": 8192, "prefer": "longest"}
    names = cell.metric_names("per_layer")
    assert set(READERS) <= set(names)
    for other in ("kimilinear.agent_turns", "dsv2lite.shared_doc",
                  "smallthinker21b.long_doc"):
        assert not set(READERS) & set(harness.Cell(other).metric_names("per_layer"))
    # judged on the gaps, the throughput and the set-up; the first token is
    # on no list: `ttft_p85_ms`'s is an accepted end-to-end entry, and the
    # accepted `gen.ttft_*` readers carry their cells in their own files
    # (an accepted test holds the manifest's lists to them)
    assert set(cell.metric_names("end_to_end")) == {
        "itl_p95_ms", "out_tok_s", "setup_s"}
    assert not {"gen.ttft_p50_ms", "gen.ttft_mean_ms"} & set(names)
    entries = {m["name"]: m for m in cell.manifest["per_layer"]}
    for name, (layer, source) in READERS.items():
        mod = cell.reader(name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.CELLS) == (
            name, "%", layer, "itl_p95_ms", [CELL])
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["source"] == source
    # the new entries stand together, behind every entry that was there
    # when they came (a later PR appends behind them: no test of this file
    # pins the end of a list)
    listed = [m["name"] for m in cell.manifest["per_layer"]]
    at = listed.index("lcf.mla_time_pct")
    assert listed[at:at + len(READERS)] == list(READERS)
    assert at > listed.index("spec.runahead_pct")
    cells = [w["name"] for w in cell.manifest["workloads"]]
    assert cells.index(CELL) > cells.index("kimilinear.agent_turns")
    entry = next(c for c in cell.manifest["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == list(cell.config["reduced"])
    assert len(cell.manifest["workloads"][cells.index(CELL)]["why"]) <= 200


def test_the_configuration_reads_as_one_of_32_chips_that_share_each_block():
    from gridllm_tpu.models.configs import get_config

    cell = harness.Cell(CELL)
    spec = cell.config
    cfg = launch_worker.model_config(spec, cell.config_name, False)
    assert (cfg.family, cfg.num_layers, cfg.attn_sublayers, cfg.cache_layers,
            cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
            cfg.num_heads, cfg.num_experts, cfg.zero_experts, cfg.router_width,
            cfg.experts_per_token, cfg.held_experts, cfg.expert_width,
            cfg.kv_lora_rank, cfg.q_lora_rank, cfg.cache_dim, cfg.mla_scales,
            cfg.router_bias, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.rope_theta) == (
        "longcat_flash", 4, 2, 8, 6144, 12_288, 131_072, 64, 512, 256, 768, 12,
        (0, 16), 2048, 512, 1536, 576, (2.0, 12 ** 0.5), True, False, 6.0, 1e7)
    assert cfg.cache_kinds == ("latent",)
    assert (cfg.vocab_held, cfg.vocab_rows) == (16_384, 16_384)
    # the vocabulary's slice by a key of its own: an accepted test
    # (test_harness.py) refuses a reduced key that ends in _size
    assert list(spec["reduced"]) == [
        "num_layers", "n_routed_experts", "vocab_held", "experts_held",
        "experts_first"]
    assert (spec["vocab_size"], spec["vocab_held"]) == (131_072, 16_384)
    # the base is the share; with the cuts put back the file is the model
    share, whole = get_config(spec["base"]), get_config("longcat-flash:560b")
    assert dataclasses.replace(cfg, name=share.name, num_layers=28) == share
    assert dataclasses.replace(share, name=whole.name, experts_held=None,
                               experts_first=None, vocab_held=None) == whole
    # a share that is not listed is refused, by the field's name
    unlisted = {**spec, "reduced": {k: v for k, v in spec["reduced"].items()
                                    if k != "experts_held"}}
    with pytest.raises(SystemExit, match="experts_held"):
        launch_worker.model_config(unlisted, "x", False)
    tiny = launch_worker.model_config(spec, "x", True)
    assert (tiny.num_layers, tiny.cache_layers, tiny.held_experts,
            tiny.zero_experts) == (2, 4, (4, 4), 8)
    assert spec["reference"]["margin_mean"] <= 0.02
    # every number of the catalog row's config stands under its key, but
    # the two of them the cut lists
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):
        row = next(r for r in map(json.loads, open(guide))
                   if r["source_url"] == spec["source"])
        for key, value in row["config"].items():
            if key not in spec["reduced"]:
                assert spec[key] == value, key
        assert spec["reduced"]["num_layers"]["from"] == row["config"]["num_layers"]
    # the floors of a cut: four blocks, 8 experts, an eighth of the vocabulary
    assert spec["num_layers"] >= 4 and spec["n_routed_experts"] >= 8
    assert spec["vocab_held"] * 8 >= spec["vocab_size"]


def test_the_costs_file_holds_the_hand_figures():
    """ISSUE 57's arithmetic of the cut, in bf16."""
    spec = harness.Cell(CELL).config
    count = costs.of(spec)
    assert count is not costs and count.__file__.endswith("longcat_flash_costs.py")
    assert count.mla_params(spec) == 90_572_800 == (
        9_437_184 + 1_536 + 18_874_368 + 3_538_944 + 512 + 8_388_608 + 50_331_648)
    assert count.dense_ffn_params(spec) == 226_492_416
    assert count.router_width(spec) == 768
    assert count.block_params_outside_experts(spec) == 638_874_368 == (
        2 * 90_572_800 + 2 * 226_492_416 + 24_576 + 4_718_592 + 768)
    assert count.expert_params(spec) == 37_748_736
    assert count.one_expert_bytes(spec) == 75_497_472
    assert count.embedding_params(spec) == 2 * 16_384 * 6144 + 6144
    assert count.total_params(spec) == 5_172_749_312 == (
        4 * (638_874_368 + 16 * 37_748_736) + 2 * 16_384 * 6144 + 6144)
    assert round(count.weight_bytes(spec) / 1e9, 2) == 10.35
    # a whole block: no chip and no four-chip host holds four of them
    whole = {**spec, "n_routed_experts": 512}
    assert round(count.block_params(whole) * 2 / 1e9, 1) == 39.9
    assert count.pool_layers(spec) == 8
    assert count.kv_bytes_per_token(spec) == 8 * 576 * 2
    assert 8 * count.STORED_ROW_VALUES * 2 == 10_240      # as the pool stores it
    assert count.kv_launch_bytes(spec, lambda name: 1000.0) == 9_216_000.0
    assert count.kv_launch_bytes(spec, lambda name: None) is None
    assert count.held_experts(spec) == 64
    assert count.step_weight_bytes(spec) == (
        5_172_749_312 - 201_332_736 + 16_384 * 6144) * 2
    assert count.step_weight_bytes(spec, 20.0) == (
        count.step_weight_bytes(spec) - 44 * 75_497_472)
    assert count.held_expert_bytes(spec, 20.0) == 20 * 75_497_472
    # the absorbed read: flops AND bytes, a key of one sublayer
    assert count.latent_attn_flops(spec, 512, 1) == 2.0 * 512 * 64 * 1088
    assert round(count.latent_attn_flops(spec, 512, 1) / 1e6, 1) == 71.3
    assert round(count.latent_attn_flops(spec, 512, 1, "expanded") / 1e6, 1) == 37.7
    assert count.latent_attn_bytes(spec, 1) == 1152.0
    # a verify launch's 5 rows of 64 heads on a row: past the chip's ridge
    ridge = (costs.peaks("TPU v5 lite")["bf16_flops_per_s"]
             / costs.peaks("TPU v5 lite")["hbm_bytes_per_s"])
    assert count.latent_attn_flops(spec, 5, 1) / 1280 == 544.0 > ridge
    assert count.chip_share(spec) == {"weights": 1, "kv": 1, "heads": 1}
    assert count.chip_share({**spec, "mesh": "tp:2"}) is None


def _reference():
    spec = harness.Cell(CELL).config
    mod_spec = importlib.util.spec_from_file_location(
        "longcat_flash_f32_t", os.path.join(BENCH, spec["reference"]["module"]))
    ref = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(ref)
    return spec, ref


def test_the_reference_imports_nothing_from_the_program():
    spec, _ = _reference()
    with open(os.path.join(BENCH, spec["reference"]["module"])) as f:
        text = f.read()
    assert "import gridllm" not in text and "from gridllm" not in text
    assert "pallas" not in text and 'default_matmul_precision("highest")' in text


def test_the_reference_agrees_with_the_program_and_every_control_fails():
    """At the tiny size, in the configuration's own type's place float32:
    the program's forward reads the reference's logits; tokens the
    reference chose itself pass `check`, and fail it with a block left
    out, the shortcut dropped, the zero-compute picks dropped, the two
    latent scales set to 1 or every weight through float8: the controls
    ISSUE 57 names for the chip. (The selection bias moves too few of 16
    tokens to fail a token check at this size: tests/test_longcat_flash.py
    holds it on the logits.)"""
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
    assert (sizes["n_routed_experts"], sizes["router_experts"],
            sizes["experts_first"], sizes["zero_expert_num"]) == (4, 16, 4, 8)
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
    for word in ("no_shortcut", "no_zero", "unit_scales",
                 "round_to=float8_e4m3fn"):
        switch = reference_controls.parse_switch(word)[1]
        got = reference_check.check(
            reference_controls.Switched(ref, **switch), params, sizes,
            cfg.vocab_size, limits, records)
        assert not got["agrees"], (switch, got)


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    """``run.py --rehearse``: tiny-longcat-flash behind the gateway, the
    broker and the worker's normal path, documents asked three times,
    held to the reference; the counters' readers give numbers, the trace's
    find nothing to read in a CPU's trace."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "14", "--trace", "1", "--rehearse",
         "--out-dir", str(tmp_path / "out")],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0, done.stdout[-3000:]
    got = line["metrics"]
    # 8 zero-compute and 4 held of the tiny router's 24 outputs
    assert 10 < got["lcf.zero_picks_pct"]["value"] < 60
    assert 2 < got["lcf.held_picks_pct"]["value"] < 40
    for name in READERS:
        if READERS[name][1] == "device_trace":
            assert name not in got
    assert got["engine.window_compiles"]["value"] == 0


# -- the readers on a synthetic run -----------------------------------------

def _metrics(hits, launches, real, padded, picks, touched, ctx, verifies):
    m = 'model="longcat-flash-omni-L4-e16"'
    return "\n".join([
        f'gridllm_prefix_cache_hits_total{{{m}}} {hits}',
        f'gridllm_engine_chunk_launches_total{{{m},width="512"}} {launches}',
        f'gridllm_engine_chunk_tokens_total{{{m},kind="real"}} {real}',
        f'gridllm_engine_chunk_tokens_total{{{m},kind="padded"}} {padded}',
        f'gridllm_moe_picks_total{{{m},where="held"}} {picks[0]}',
        f'gridllm_moe_picks_total{{{m},where="absent"}} {picks[1]}',
        f'gridllm_moe_picks_total{{{m},where="zero"}} {picks[2]}',
        f'gridllm_moe_experts_touched_total{{{m}}} {touched}',
        f'gridllm_engine_verify_ctx_tokens_total{{{m}}} {ctx}',
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
        "verify/ragged": op(
            v, "%ragged_attention.2 = bf16[16,1,320,512]{3,2,1,0} custom-call(", 0.120),
        "verify/absorb": op(
            v, "%fusion.50 = f32[16,5,64,512]{3,2,1,0} fusion(bf16[16,5,64,128], bf16[512,64,128]", 0.040),
        "mixed/ragged": op(
            mx, "%ragged_attention.5 = (bf16[1,33792,512]{2,1,0}, bf16[16,1,64,512]) custom-call(", 0.200),
        "mixed/absorb": op(
            mx, "%fusion.61 = f32[64,512,528]{2,1,0} fusion(bf16[528,64,128], bf16[512,64,256]", 0.020),
        # the low-rank query: W_qa, the norm between, W_qb
        "verify/w_qa": op(
            v, "%fusion.11 = bf16[16,5,1536]{2,1,0} fusion(bf16[16,5,6144], bf16[6144,1536]", 0.020),
        "verify/w_qb": op(
            v, "%fusion.12 = f32[16,5,12288]{2,1,0} fusion(bf16[16,5,1536], bf16[1536,12288]", 0.030),
        "mixed/q_norm": op(
            mx, "%fusion.13 = bf16[1,528,1536]{2,1,0} fusion(bf16[1,528,1536]", 0.004),
        # the dense SwiGLU is 12,288 wide too: not the query's
        "verify/dense_up": op(
            v, "%fusion.20 = bf16[16,5,12288]{2,1,0} fusion(bf16[16,5,6144], bf16[6144,12288]", 0.200),
        # the held experts: the kernel in a verify launch, sorted in a mixed
        "verify/grouped": op(
            v, "%grouped_experts.3 = f32[80,6144]{1,0} custom-call(", 0.080),
        "mixed/ragged_dot": op(
            mx, "%ragged-dot.4 = bf16[6336,2048]{1,0} custom-call(bf16[6336,6144]", 0.050),
        # the router (768 wide) and the zero-compute sum are no product of
        # a held expert
        "verify/router": op(
            v, "%fusion.30 = f32[16,5,768]{2,1,0} fusion(f32[16,5,6144], f32[6144,768]", 0.010),
    }
    return {
        "config": harness.Cell(CELL).config,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "requests": [types.SimpleNamespace(group=i // 3) for i in range(30)],
        "pool": {"pageSize": 128},
        "trace": {
            "devices": {"/device:TPU:0": {"busy_s": 1.0, "idle_pct": 50.0}},
            "programs": {v: {"seconds": 0.6, "count": 40},
                         mx: {"seconds": 0.4, "count": 10}},
            "ops": ops},
        "worker_before": _metrics(0, 0, 0, 0, (0, 0, 0), 0, 0, 0),
        "worker_after": _metrics(960, 140, 62_000, 71_680, (210, 6490, 3300),
                                 9000, 0, 300),
        "trace_counters": (
            _metrics(0, 0, 0, 0, (0, 0, 0), 1000, 100_000, 20),
            _metrics(0, 0, 0, 0, (0, 0, 0), 1800, 2_500_000, 60)),
        "samples": [],
    }


def test_the_readers_on_a_synthetic_trace(synthetic):
    import mla

    cell = harness.Cell(CELL)
    got = {name: cell.reader(name).compute(synthetic) for name in READERS}
    spec, count = synthetic["config"], costs.of(synthetic["config"])
    peaks = costs.peaks("TPU v5 lite")
    assert got["lcf.mla_time_pct"] == pytest.approx(100.0 * (
        0.120 + 0.040 + 0.200 + 0.020))
    # W_qa, W_qb and the norm between; not the dense SwiGLU of 12,288
    assert {o["key"] for o in lcf.qlora_ops(synthetic)} == {
        "verify/w_qa", "verify/w_qb", "mixed/q_norm"}
    assert got["lcf.qlora_time_pct"] == pytest.approx(100.0 * 0.054)
    assert got["lcf.held_time_pct"] == pytest.approx(100.0 * (0.080 + 0.050))
    assert got["lcf.zero_picks_pct"] == pytest.approx(33.0)
    assert got["lcf.held_picks_pct"] == pytest.approx(2.1)
    # 800 held experts touched over the capture's 40 launches: 20 a launch
    # of the 64 held, over the 2 ms a launch the kernel takes
    assert got["lcf.held_mem_roofline_pct"] == pytest.approx(
        100.0 * 20 * 75_497_472 / peaks["hbm_bytes_per_s"] / (0.080 / 40))
    # 60,000 context positions a launch x 9,216 B against 5 x 64 query
    # rows over 8 pool layers: the flops bind
    ctx = 2_400_000 / 40
    flops = 8 * (count.latent_attn_flops(spec, 5.0, ctx)
                 + count.absorb_flops(spec, 5.0))
    assert flops / peaks["bf16_flops_per_s"] > (
        ctx * 9216 / peaks["hbm_bytes_per_s"])
    assert got["lcf.mla_decode_roofline_pct"] == pytest.approx(
        100.0 * flops / peaks["bf16_flops_per_s"] / (0.160 / 40))
    # 512 padded rows a launch over the mean context a launch attends
    attended = mla.chunk_context(synthetic)
    least = 8 * (count.latent_attn_flops(spec, 512.0, attended)
                 + count.absorb_flops(spec, 512.0)) / peaks["bf16_flops_per_s"]
    assert got["lcf.mla_chunk_roofline_pct"] == pytest.approx(
        100.0 * least / (0.220 / 10))
    for name in ("lcf.mla_chunk_roofline_pct", "lcf.mla_decode_roofline_pct",
                 "lcf.held_mem_roofline_pct"):
        assert 0 < got[name] < 100, (name, got[name])
    # the accepted readers with no list read this cell through the costs file
    for name in ("step.verify_mem_mfu_pct", "kernel.ragged_decode_roofline_pct"):
        assert 0 < cell.reader(name).compute(synthetic) < 100, name


def test_a_program_without_the_kernels_or_counters_reads_as_nothing(synthetic):
    """The parent's trace and scrape, or another family's configuration:
    every reader returns None and none raises."""
    cell = harness.Cell(CELL)
    other = {**synthetic, "config": harness.Cell("kimilinear.agent_turns").config}
    bare = {**synthetic, "trace": {}, "worker_before": "", "worker_after": "",
            "trace_counters": None, "samples": []}
    for name in READERS:
        assert cell.reader(name).compute(bare) is None, name
        assert cell.reader(name).compute(other) is None, name
