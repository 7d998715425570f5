"""The granite-4.0-h-micro configuration, its cell, its mix, its costs
file, its reference module and its four readers: found by name with no
edit to a file that was there, held to ISSUE 61's hand figures, the
reference held to the program's forward at the tiny size with controls
that fail, and the readers run on a made-up run (operations as a trace
names them)."""
import dataclasses
import importlib.util
import os
import statistics

import pytest

import costs
import launch_worker
import run as harness
import ssm
import trafficgen
from conftest import BENCH

CELL = "granite4hmicro.long_answers"
READERS = ("ssm.time_pct", "ssm.step_roofline_pct", "ssm.chunk_roofline_pct",
           "ssm.state_bytes_pct")


def test_the_cell_and_its_files_are_found_by_name():
    cell = harness.Cell(CELL)
    assert cell.config_name == "granite-4.0-h-micro" and cell.chips == 1
    assert cell.rate > 0
    stream, = cell.mix["streams"]
    assert stream["group_offsets_s"] == [0] and stream["shared_tokens"] is None
    assert cell.mix["bursts"] is None
    assert stream["own_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.7, "min": 32, "max": 512}
    assert stream["output_tokens"] == {"dist": "lognormal", "median": 160,
                                       "sigma": 0.35, "min": 64, "max": 256}
    assert set(READERS) <= set(cell.metric_names("per_layer"))
    for other in ("mistral7b.chat", "olmohybrid7b.agent_turns"):
        assert not set(READERS) & set(harness.Cell(other).metric_names("per_layer"))
    # the first token waits for a benchmark PR: on no accepted entry's list
    assert set(cell.metric_names("end_to_end")) == {
        "itl_p95_ms", "out_tok_s", "setup_s"}
    for m in cell.manifest["end_to_end"] + cell.manifest["per_layer"]:
        if m["name"] not in READERS:
            assert CELL not in m.get("workloads", [])
    entries = {m["name"]: m for m in cell.manifest["per_layer"]}
    for name in READERS:
        mod = cell.reader(name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.CELLS) == (
            name, "%", "recurrent state", "itl_p95_ms", [CELL])
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["layer"] == "recurrent state"
        assert "100" in mod.__doc__        # says why it cannot pass 100
    spec = cell.config
    assert spec["reduced"] == {} and spec["env"]["GRIDLLM_MAX_BATCH_SLOTS"] == "48"
    assert next(c for c in cell.manifest["configs"]
                if c["name"] == cell.config_name)["reduced"] == []
    cfg = launch_worker.model_config(spec, cell.config_name, False)
    from gridllm_tpu.models.configs import get_config

    assert dataclasses.replace(cfg, name=spec["base"]) == get_config(spec["base"])
    assert (cfg.num_layers, cfg.linear_layers, cfg.cache_layers,
            cfg.vocab_size) == (40, 36, 4, 100_352)


def test_the_costs_file_holds_the_hand_figures():
    """ISSUE 61's arithmetic, in bf16."""
    spec = harness.Cell(CELL).config
    count = costs.of(spec)
    assert count is not costs and count.__file__.endswith("granite_hybrid_costs.py")
    assert count.conv_channels(spec) == 4_352
    assert count.mamba_layer_params(spec) == 76_182_976
    assert count.mamba_layer_params(spec) - count.mlp_params(spec) - 4_096 == 25_847_232
    assert count.mlp_params(spec) == 50_331_648
    assert count.attention_layer_params(spec) == 60_821_504
    assert count.layer_counts(spec) == (36, 4)
    assert count.embedding_params(spec) == 205_520_896 + 2_048
    assert count.total_params(spec) == 3_191_396_096
    assert round(count.weight_bytes(spec) / 1e9, 2) == 6.38
    assert count.kv_bytes_per_token(spec) == 8_192
    # the 64-wide head at 128 lanes, as the chip's pool stores it
    assert count.kv_stored_bytes_per_token(spec) == 16_384
    assert count.state_bytes_per_slot(spec) == 75_497_472
    assert count.state_bytes_per_slot(spec, conv=True) == 75_497_472 + 940_032
    assert count.step_weight_bytes(spec) == (3_191_396_096 - 2_048) * 2
    # the equations: 5 P N a token, head and Mamba-2 layer
    assert count.ssd_chunk_flops(spec, 512) == 512 * 36 * 64 * 5.0 * 64 * 128
    assert count.ssd_chunk_bytes(spec, 512) == 36 * (
        2 * 64 * 64 * 128 * 4 + 512 * (2 * 4096 + 256 + 64) * 4)
    # a live slot's state in and out and its rows' x, B, C, dt, every layer
    assert count.ssd_step_bytes(spec, 30, 5) == 30 * 36 * (
        2 * 64 * 64 * 128 * 4 + 5 * (4096 + 256 + 64) * 4)
    assert not hasattr(count, "kv_launch_bytes")
    assert count.chip_share(spec) == {"weights": 1, "kv": 1, "heads": 1}
    assert count.chip_share({**spec, "mesh": "tp:2"}) is None
    # ISSUE 61's shares: from 18 live slots on the Mamba-2 layers' own bytes
    # are over half of a launch's; at 32 the state alone is 43 %
    mamba = 36 * (76_182_976 - 50_331_648) * 2
    for n, over in ((17, False), (18, True)):
        own = mamba + n * 2 * 75_497_472
        assert (own > 0.5 * (count.step_weight_bytes(spec) + n * 2 * 75_497_472)) == over
    assert round(32 * 2 * 75_497_472 / (
        count.step_weight_bytes(spec) + 32 * 2 * 75_497_472), 2) == 0.43


def test_the_mix_is_the_one_the_issue_names():
    """Prompts 32-512 median 128, answers 64-256 median 160, Poisson, no
    sharing, no bursts: the generator's own draws over three seeds."""
    mix = harness.Cell(CELL).mix
    prompts, outs, gaps = [], [], []
    for seed in (1, 2_147_483_777, 3_000_000_019):
        reqs = trafficgen.generate(mix, 4.0, 200.0, seed)
        assert all(r.shared_bytes == 0 for r in reqs)
        assert len({r.prompt[:64] for r in reqs}) == len(reqs)
        prompts += [len(r.prompt) for r in reqs]
        outs += [r.num_predict for r in reqs]
        due = sorted(r.due_s for r in reqs)
        gaps += [b - a for a, b in zip(due, due[1:])]
    assert (min(prompts), max(prompts)) == (32, 512)
    assert (min(outs), max(outs)) == (64, 256)
    assert 118 <= statistics.median(prompts) <= 138
    assert 152 <= statistics.median(outs) <= 168
    assert 150 <= statistics.mean(prompts) <= 176      # "about 163 in"
    assert 160 <= statistics.mean(outs) <= 180         # "about 170 out"
    assert all(o > 0 for o in outs)
    # Poisson-like as the generator lays it out: an arrival mid-gap, so a
    # spacing is the mean of two exponential gaps (deviation 0.71 of it)
    assert 0.6 < statistics.pstdev(gaps) / statistics.mean(gaps) < 0.85


def _reference():
    spec = harness.Cell(CELL).config
    mod_spec = importlib.util.spec_from_file_location(
        "granite_hybrid_f32_t", os.path.join(BENCH, spec["reference"]["module"]))
    ref = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(ref)
    return spec, ref


def test_the_reference_imports_nothing_from_the_program():
    spec, _ = _reference()
    with open(os.path.join(BENCH, spec["reference"]["module"])) as f:
        text = f.read()
    assert "import gridllm" not in text and "from gridllm" not in text


def test_the_reference_agrees_with_the_program_and_every_control_fails():
    """At the tiny size in float32: the program's forward reads the
    reference's logits; tokens the reference chose itself pass `check`,
    and fail it with a layer left out or any one mechanism switched."""
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
    assert sizes["mamba_d_state"] == 16 and sizes["attention_multiplier"] == 0.125
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
    limits = {"margin_abs": 1e-4, "margin_rel": 0.0, "margin_mean": 1e-5}
    sound = reference_check.check(ref, params, sizes, cfg.vocab_size, limits,
                                  records)
    assert sound["agrees"] and sound["records"][0]["worst_shortfall"] < 1e-4
    skipped = reference_check.check(ref, params, sizes, cfg.vocab_size, limits,
                                    records, skip_layer=cfg.num_layers // 2)
    assert not skipped["agrees"]
    # (a rotary embedding on the tiny preset's two attention layers moves
    # the logits by 0.016 and overturns none of 16 greedy tokens:
    # tests/test_granite_hybrid.py holds it on the logits themselves)
    for word in ("no_conv", "no_decay", "no_skip", "attn_scale_rsqrt",
                 "embedding_multiplier=1", "residual_multiplier=1",
                 "round_to=float8_e4m3fn"):
        switch = reference_controls.parse_switch(word)[1]
        got = reference_check.check(
            reference_controls.Switched(ref, **switch), params, sizes,
            cfg.vocab_size, limits, records)
        assert not got["agrees"], (switch, got)


# -- the readers on a made-up run --------------------------------------------

def _metrics(launches, padded, occupancy, verify, ctx):
    m = 'model="granite-4.0-h-micro"'
    return "\n".join([
        f'gridllm_engine_chunk_launches_total{{{m},width="512"}} {launches}',
        f'gridllm_engine_chunk_tokens_total{{{m},kind="padded"}} {padded}',
        f'gridllm_engine_batch_occupancy_bucket{{{m},le="+Inf"}} {occupancy[1]}',
        f'gridllm_engine_batch_occupancy_sum{{{m}}} {occupancy[0]}',
        f'gridllm_engine_batch_occupancy_count{{{m}}} {occupancy[1]}',
        f'gridllm_engine_phase_seconds_sum{{{m},phase="dispatch_verify"}} 1.0',
        f'gridllm_engine_phase_seconds_count{{{m},phase="dispatch_verify"}} {verify}',
        f'gridllm_engine_verify_ctx_tokens_total{{{m}}} {ctx}',
    ]) + "\n"


@pytest.fixture(scope="module")
def made_up():
    def op(program, text, seconds):
        return {"program": program, "text": text, "seconds": seconds,
                "total_seconds": seconds, "count": 10}

    ops = {
        "mixed/ssd_chunk": op(
            "jit_mixed_chunk_fn",
            "%ssd_chunk.3 = (f32[8,64,4096]{2,1,0}, f32[128,4096]{1,0}) custom-call(", 0.030),
        "mixed/ssd_step": op(
            "jit_mixed_chunk_fn",
            "%ssd_step.5 = (f32[36,48,128,4096]{3,2,1,0}) custom-call(", 0.020),
        "verify/ssd_step": op(
            "jit_verify_block_fn",
            "%ssd_step.9 = (f32[36,48,128,4096]{3,2,1,0}, f32[48,8,4096]) custom-call(", 0.400),
        "verify/conv": op(
            "jit_verify_block_fn",
            "%fusion.12 = f32[48,5,4352]{2,1,0} fusion(bf16[48,8,4352]", 0.020),
        "verify/gate": op(
            "jit_verify_block_fn",
            "%fusion.40 = bf16[48,5,4096]{2,1,0} fusion(f32[48,5,64,64]", 0.010),
        "verify/pairs": op(
            "jit_verify_block_fn",
            "%fusion.41 = f32[48,1,64,8,8]{4,3,2,1,0} fusion(f32[48,8,128]", 0.005),
        "verify/ragged": op(
            "jit_verify_block_fn",
            "%ragged_attention.2 = bf16[48,8,20,64]{3,2,1,0} custom-call(", 0.050),
        "verify/mlp": op(
            "jit_verify_block_fn",
            "%fusion.77 = bf16[240,8192]{1,0} fusion(bf16[240,2048]", 0.300),
        # the layer's projections: shapes of the layer, but plain products
        "verify/w_in": op(
            "jit_verify_block_fn",
            "%fusion.1131 = bf16[48,5,8512]{2,0,1} fusion(bf16[48,5,2048]{2,0,1} "
            "%fusion.1128, bf16[2048,8512]{1,0} %get-tuple-element.4170", 0.060),
        "verify/w_out": op(
            "jit_verify_block_fn",
            "%fusion.1121 = bf16[48,5,2048]{2,0,1} fusion("
            "bf16[48,5,4096]{2,0,1} %reshape.2559, bf16[4096,2048]", 0.040),
        "mixed/own": op(
            "jit_mixed_chunk_fn",
            "%fusion.1682 = f32[8,64,64,64]{3,2,1,0} fusion(f32[8,64,128]", 0.015),
    }
    return {
        "config": harness.Cell(CELL).config,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "trace": {
            "devices": {"/device:TPU:0": {"busy_s": 1.0, "idle_pct": 50.0}},
            "programs": {"jit_verify_block_fn": {"seconds": 0.9, "count": 20},
                         "jit_mixed_chunk_fn": {"seconds": 0.1, "count": 10}},
            "ops": ops},
        "worker_before": _metrics(0, 0, (0, 0), 0, 0),
        "worker_after": _metrics(200, 200 * 512, (9000, 300), 300, 2_000_000),
        "trace_counters": (_metrics(0, 0, (300, 10), 10, 100_000),
                           _metrics(0, 0, (900, 30), 30, 220_000)),
        "samples": [],
    }


def test_the_readers_on_a_made_up_run(made_up):
    cell = harness.Cell(CELL)
    got = {name: cell.reader(name).compute(made_up) for name in READERS}
    spec, count = made_up["config"], costs.of(made_up["config"])
    peaks = costs.peaks("TPU v5 lite")
    # both kernels, the convolution, the gate, the pair terms; not
    # attention, not the MLP, and neither of the layer's projections
    assert {o["key"] for o in ssm.layer_ops(made_up)} == {
        "mixed/ssd_chunk", "mixed/ssd_step", "verify/ssd_step", "verify/conv",
        "verify/gate", "verify/pairs", "mixed/own"}
    assert got["ssm.time_pct"] == pytest.approx(
        100.0 * (0.030 + 0.020 + 0.400 + 0.020 + 0.010 + 0.005 + 0.015))
    # 30 live slots a launch over the capture, K + 1 = 5 rows, 20 ms
    assert got["ssm.step_roofline_pct"] == pytest.approx(
        100.0 * count.ssd_step_bytes(spec, 30.0, 5) / peaks["hbm_bytes_per_s"]
        / (0.400 / 20))
    # 512 padded rows a launch over the kernel's 3 ms: the bytes bound it
    assert count.ssd_chunk_bytes(spec, 512) / peaks["hbm_bytes_per_s"] > (
        count.ssd_chunk_flops(spec, 512) / peaks["bf16_flops_per_s"])
    assert got["ssm.chunk_roofline_pct"] == pytest.approx(
        100.0 * count.ssd_chunk_bytes(spec, 512) / peaks["hbm_bytes_per_s"]
        / (0.030 / 10))
    # 30 live slots' state in and out beside the weights and 6,000 tokens
    # of pages a launch
    state = 2 * 30 * 75_497_472
    assert got["ssm.state_bytes_pct"] == pytest.approx(
        100.0 * state / (state + count.step_weight_bytes(spec) + 6_000 * 16_384))
    for name in READERS:
        assert 0 < got[name] < 100, name


def test_a_program_without_the_kernels_or_counters_reads_as_nothing(made_up):
    """The parent's trace and scrape, or another family's configuration:
    every reader returns None and none raises."""
    cell = harness.Cell(CELL)
    dense = {**made_up, "config": harness.Cell("mistral7b.chat").config}
    bare = {**made_up, "trace": {}, "worker_before": "", "worker_after": "",
            "trace_counters": None, "samples": []}
    still = {**made_up, "trace_counters": (made_up["trace_counters"][0],) * 2}
    for name in READERS:
        assert cell.reader(name).compute(bare) is None, name
        assert cell.reader(name).compute(dense) is None, name
    # a counter that did not move over the capture
    assert cell.reader("ssm.state_bytes_pct").compute(still) is None
    assert cell.reader("ssm.step_roofline_pct").compute(still) is None
