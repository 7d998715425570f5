"""The DeepSeek-V2-Lite configuration, its cell, its costs file, its
reference module and its seven readers: found by name with no edit to a
file that was there, held to ISSUE 36's hand figures, rehearsed on the
CPU, and the readers run on what the chip recorded
(``data/dsv2lite_shared_doc.json``: cut from a traced run of the cell,
PR 36)."""
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
import run as harness
from conftest import BENCH, ROOT

CELL = "dsv2lite.shared_doc"
READERS = {"mla.time_pct": ("latent attention", "itl_p95_ms"),
           "mla.decode_roofline_pct": ("latent attention", "itl_p95_ms"),
           "mla.chunk_roofline_pct": ("latent attention", "itl_p95_ms"),
           "kv.latent_row_pct": ("KV pool", "out_tok_s"),
           "experts.time_pct": ("routed experts", "itl_p95_ms"),
           "experts.mem_roofline_pct": ("routed experts", "itl_p95_ms"),
           "experts.touched_pct": ("routed experts", "itl_p95_ms")}


def test_the_cell_and_its_files_are_found_by_name():
    cell = harness.Cell(CELL)
    assert cell.config_name == "deepseek-v2-lite-L10" and cell.chips == 1
    assert cell.mix == harness.Cell("mistral7b.shared_doc").mix   # the same file
    assert cell.rate > 0
    names = cell.metric_names("per_layer")
    assert set(READERS) <= set(names)
    # no accepted entry is edited for the cell: a metric that lists other
    # cells is not asked of this one
    assert "engine.prefix_hit_pct" not in names
    # no other cell is asked for this family's metrics
    for other in ("mistral7b.shared_doc", "smallthinker21b.chat"):
        assert not set(READERS) & set(harness.Cell(other).metric_names("per_layer"))
    # the median first token stands between the mix's two modes here and
    # is read per layer (PERF.md section 2)
    assert set(cell.metric_names("end_to_end")) == {
        "itl_p95_ms", "out_tok_s", "setup_s"}
    assert {"gen.ttft_p50_ms", "gen.ttft_mean_ms"} <= set(names)
    entries = {m["name"]: m for m in cell.manifest["per_layer"]}
    for name, (layer, moves) in READERS.items():
        mod = cell.reader(name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.CELLS) == (
            name, "%", layer, moves, [CELL])
        assert entries[name]["workloads"] == [CELL]
    spec = cell.config
    cfg = launch_worker.model_config(spec, cell.config_name, False)
    assert (cfg.family, cfg.num_layers, cfg.first_k_dense, cfg.num_experts,
            cfg.experts_per_token, cfg.num_shared_experts, cfg.expert_width,
            cfg.intermediate_size, cfg.hidden_size, cfg.num_heads,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.vocab_size, cfg.norm_topk_prob) == (
        "deepseek_v2", 10, 1, 64, 6, 2, 1408, 10_944, 2048, 16, 512, 128, 64,
        128, 102_400, False)
    assert cfg.rope_scaling.rope_type == "yarn" and cfg.rope_scaling.factor == 40
    assert (cfg.cache_heads, cfg.cache_dim) == (1, 576)
    assert list(spec["reduced"]) == ["num_hidden_layers"]
    # held to `base` with the depth put back
    from gridllm_tpu.models.configs import get_config

    assert dataclasses.replace(
        cfg, name="deepseek-v2-lite:16b", num_layers=27) == get_config(spec["base"])
    assert spec["reference"]["margin_mean"] <= 0.02


def test_the_costs_file_holds_the_hand_figures():
    """ISSUE 36's arithmetic, in bf16."""
    spec = harness.Cell(CELL).config
    count = costs.of(spec)
    assert count is not costs and count.__file__.endswith("deepseek_v2_costs.py")
    assert count.attention_params(spec) == 13_763_072
    assert count.expert_params(spec) == 8_650_752
    assert count.dense_layer_params(spec) == 81_007_104
    assert count.routed_layer_params(spec) == 584_847_872
    assert count.embedding_params(spec) == 419_432_448
    assert count.total_params(spec) == 5_764_070_400
    assert count.weight_bytes(spec) == 11_528_140_800
    assert count.kv_bytes_per_token(spec) == 11_520
    assert count.per_head_row_values(spec) * 2 == 10_240
    assert count.STORED_ROW_VALUES * 2 * spec["num_hidden_layers"] == 12_800
    assert count.expert_bytes(spec) == 9 * 64 * 8_650_752 * 2
    # layer 0, nine expert layers whole and the head: 11.1 GB, 13.6 ms
    assert count.step_weight_bytes(spec) == (
        81_007_104 + 9 * 584_847_872 + 102_400 * 2048) * 2
    assert round(count.step_weight_bytes(spec) / 819e9 * 1e3, 1) == 13.6
    # the latent read: 2 x rows x ctx x (576 + 512) a head absorbed; 2 x rows
    # x ctx x (192 + 128) a head and the up-projection 2 x ctx x 512 x 4096
    assert count.latent_attn_flops(spec, 5, 1000) == 2.0 * 5 * 1000 * 16 * 1088
    assert count.latent_attn_flops(spec, 512, 1000, "expanded") == (
        2.0 * 512 * 1000 * 16 * 320 + 2.0 * 1000 * 512 * 4096)
    assert count.absorb_flops(spec, 80) == 2.0 * 80 * 16 * 512 * 256
    assert count.latent_attn_bytes(spec, 1000) == 1000 * 1152
    assert count.chip_share(spec) == {"weights": 1, "kv": 1, "heads": 1}
    assert count.chip_share({**spec, "mesh": "tp:4"}) is None
    # whole depth: the 15.7 B of the model's description
    assert round(count.total_params({**spec, "num_hidden_layers": 27}) / 1e9, 1) == 15.7


def _reference():
    spec = harness.Cell(CELL).config
    mod_spec = importlib.util.spec_from_file_location(
        "deepseek_v2_f32_t", os.path.join(BENCH, spec["reference"]["module"]))
    ref = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(ref)
    return spec, ref


def test_the_reference_imports_nothing_from_the_program():
    spec, _ = _reference()
    with open(os.path.join(BENCH, spec["reference"]["module"])) as f:
        text = f.read()
    assert "import gridllm" not in text and "from gridllm" not in text


def test_the_control_fails_and_the_sound_check_agrees(tmp_path):
    """``reference_check.py --rehearse --control`` on tokens the reference
    chose itself on tiny-deepseek-v2: agrees; with a layer left out it
    fails, and so do the top-k renormalised, no shared expert and plain
    RoPE (the module's own switches)."""
    import jax
    import jax.numpy as jnp

    import loadgen
    import reference_check
    import reference_controls
    from gridllm_tpu.engine.engine import _model_module
    from gridllm_tpu.models.configs import get_config

    spec, ref = _reference()
    cfg = get_config(spec["rehearse_base"])
    params = _model_module(cfg).init_params(
        cfg, jax.random.PRNGKey(0), getattr(jnp, spec["dtype"]))
    sizes = reference_check.reference_sizes(ref, cfg, spec, rehearse=True)
    assert sizes["kv_lora_rank"] == 32 and sizes["rope_scaling"]["factor"] == 4.0
    # past YaRN's original context of the tiny preset (64)
    seq = [int(t) for t in jax.random.randint(jax.random.PRNGKey(7), (80,), 0, 256)]
    for _ in range(16):           # greedy under the penalty the benchmark asks for
        row = ref.logits(params, sizes, seq)[-1:]
        row = ref.penalized(row, seq, len(seq), loadgen.REPEAT_PENALTY,
                            loadgen.REPEAT_LAST_N)
        seq.append(int(row[0].argmax()))
    records = [{"index": 0, "context": seq, "n_prompt": 80}]
    (tmp_path / "records.json").write_text(json.dumps(records))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": ROOT + os.pathsep + BENCH}
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference_check.py"), "--config",
         harness.Cell(CELL).config_file, "--records",
         str(tmp_path / "records.json"), "--rehearse", "--control"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    out = json.loads([x for x in done.stdout.splitlines()
                      if x.startswith("REFERENCE=")][-1][len("REFERENCE="):])
    assert out["agrees"] and out["records"][0]["worst_shortfall"] < 1e-4, out
    assert out["layer_skipped_fails"], out["layer_skipped"]

    class Broken:
        """The reference with one mechanism broken, as `check` calls it."""

        def __init__(self, **switch):
            self.switch = switch

        def logits(self, params, sizes, tokens, skip_layer=None):
            return ref.logits(params, sizes, tokens, **self.switch)

        margins = staticmethod(ref.margins)

    # (float8 weights are the chip's control: on the tiny preset's nearly
    # flat logits they read a mean of 0.0105 against the limit of 0.01,
    # too near to hold a test to; the configuration's file has the chip's)
    for word in ("renormalise_topk", "no_shared", "rope=plain"):
        switch = reference_controls.parse_switch(word)[1]
        got = reference_check.check(Broken(**switch), params, sizes,
                                    cfg.vocab_size, spec["reference"], records)
        assert not got["agrees"], (switch, got)


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "14", "--trace", "1", "--rehearse",
         "--out-dir", str(tmp_path / "out")],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, done.stdout[-3000:]
    # the gauges' reader gives a number (the tiny preset's row: 48 values of
    # 4 x (32 + 16) per head, unpadded); the trace readers find nothing to
    # read in a CPU's trace (its events carry no HLO line)
    assert line["metrics"]["kv.latent_row_pct"]["value"] == pytest.approx(25.0)
    assert line["metrics"]["experts.touched_pct"]["value"] > 0
    for name in ("mla.time_pct", "mla.decode_roofline_pct",
                 "mla.chunk_roofline_pct", "experts.time_pct",
                 "experts.mem_roofline_pct"):
        assert name not in line["metrics"]
    assert line["metrics"]["engine.window_compiles"]["value"] == 0


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "tests", "data", "dsv2lite_shared_doc.json")) as f:
        run = json.load(f)
    run["config"] = harness.Cell(CELL).config
    run["requests"] = [types.SimpleNamespace(group=g) for g in run.pop("groups")]
    return run


def test_the_readers_on_what_the_chip_recorded(recorded):
    cell = harness.Cell(CELL)
    got = {name: cell.reader(name).compute(recorded) for name in READERS}
    # (the record's capture ends were cut to the series PR 36's readers
    # read, the touched counter not among them: experts.mem_roofline_pct
    # charges it every expert held, as PR 36 read it)
    want = recorded["read_on_the_chip"]
    for name in READERS:
        assert got[name] == pytest.approx(want[name], rel=1e-9), name
        assert 0 < got[name] <= 100
    import mla

    # the latent reads and the absorb products, and nothing of the experts
    # or the head among them; the experts' products, and no attention
    texts = [o["text"] for o in mla.latent_ops(recorded)]
    assert any("ragged_attention" in t for t in texts)
    assert not any("1408" in t or "102400" in t for t in texts)
    texts = [o["text"] for o in mla.expert_ops(recorded)]
    assert texts and not any("ragged_attention" in t or "102400" in t for t in texts)


def test_a_program_without_the_operations_or_counters_reads_as_nothing(recorded):
    """The parent's trace and scrape, or another family's configuration:
    every reader returns None and none raises."""
    cell = harness.Cell(CELL)
    dense = {**recorded, "config": harness.Cell("mistral7b.shared_doc").config}
    bare = {**recorded, "trace": {}, "worker_before": "", "worker_after": "",
            "trace_counters": None}
    for name in READERS:
        assert cell.reader(name).compute(bare) is None, name
        if name != "kv.latent_row_pct":     # a gauge of the program, not a shape
            assert cell.reader(name).compute(dense) is None, name
