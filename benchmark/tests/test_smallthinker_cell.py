"""The SmallThinker configuration, its cell, its costs file, its reference
module and its three readers: found by name with no edit to a file that
was there, held to ISSUE 33's hand figures, rehearsed on the CPU, and the
readers run on what the chip recorded (``data/smallthinker_chat.json``:
cut from a traced run of the cell, PR 33)."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

import costs
import launch_worker
import run as harness
from conftest import BENCH, ROOT

CELL = "smallthinker21b.chat"
CELLS = [CELL, "smallthinker21b.long_doc"]     # the second since PR 55
READERS = ("moe.time_pct", "moe.expert_mem_roofline_pct",
           "moe.experts_touched_pct")


def test_the_cell_and_its_files_are_found_by_name():
    cell = harness.Cell(CELL)
    assert cell.config_name == "smallthinker-21b-a3b-L12" and cell.chips == 1
    assert cell.mix == harness.Cell("mistral7b.chat").mix     # the same file
    assert cell.rate > 0
    names = cell.metric_names("per_layer")
    assert set(READERS) <= set(names)
    # no other cell is asked for the routed layer's metrics
    assert not set(READERS) & set(harness.Cell("mistral7b.chat").metric_names("per_layer"))
    assert set(cell.metric_names("end_to_end")) == {
        "ttft_p50_ms", "itl_p95_ms", "out_tok_s", "setup_s"}
    entries = {m["name"]: m for m in cell.manifest["per_layer"]}
    for name in READERS:
        mod = cell.reader(name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.CELLS) == (
            name, "%", "routed experts", "itl_p95_ms", CELLS)
        assert entries[name]["workloads"] == CELLS
    spec = cell.config
    cfg = launch_worker.model_config(spec, cell.config_name, False)
    assert (cfg.family, cfg.num_layers, cfg.num_experts, cfg.experts_per_token,
            cfg.intermediate_size, cfg.hidden_size, cfg.sliding_window,
            cfg.vocab_size, cfg.rope_theta) == (
        "smallthinker", 12, 64, 6, 768, 2560, 4096, 151_936, 1.5e6)
    assert cfg.layer_windows == (0, 4096, 4096, 4096) * 3
    assert list(spec["reduced"]) == ["num_hidden_layers"]


def test_the_costs_file_holds_the_hand_figures():
    """ISSUE 33's arithmetic, in bf16."""
    spec = harness.Cell(CELL).config
    count = costs.of(spec)
    assert count is not costs and count.__file__.endswith(
        "smallthinker_costs.py")
    assert count.expert_params(spec) == 5_898_240
    assert count.layer_params(spec) == 398_627_840
    assert count.embedding_params(spec) == 777_914_880
    assert count.total_params(spec) == 5_561_448_960
    assert count.weight_bytes(spec) == 11_122_897_920
    assert count.kv_bytes_per_token(spec) == 24_576
    assert count.expert_bytes(spec) == 12 * 64 * 5_898_240 * 2
    # every layer whole and the head: 10.35 GB, 12.6 ms at 819 GB/s
    assert count.step_weight_bytes(spec) == (12 * 398_627_840 + 151_936 * 2560) * 2
    assert round(count.step_weight_bytes(spec) / 819e9 * 1e3, 1) == 12.6
    assert count.flash_prefill_flops(spec, 1024) == 2.0 * 28 * 1024 * 1024 * 128
    assert count.chip_share(spec) == {"weights": 1, "kv": 1, "heads": 1}
    assert count.chip_share({**spec, "mesh": "ep:4"}) is None
    # whole depth: the 21.5 B of the model's name
    assert round(count.total_params({**spec, "num_hidden_layers": 52}) / 1e9, 1) == 21.5


def _reference():
    spec = harness.Cell(CELL).config
    mod_spec = importlib.util.spec_from_file_location(
        "smallthinker_f32_t", os.path.join(BENCH, spec["reference"]["module"]))
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
    chose itself on tiny-smallthinker: agrees; with a layer left out it
    fails, and so do RoPE in the global layers, no window, and a router
    fed the post-attention state (the module's own switches)."""
    import jax
    import jax.numpy as jnp

    import loadgen
    import reference_check
    from gridllm_tpu.engine.engine import _model_module
    from gridllm_tpu.models.configs import get_config

    spec, ref = _reference()
    cfg = get_config(spec["rehearse_base"])
    params = _model_module(cfg).init_params(
        cfg, jax.random.PRNGKey(0), getattr(jnp, spec["dtype"]))
    sizes = reference_check.reference_sizes(ref, cfg, spec, rehearse=True)
    assert sizes["sliding_window_layout"] == [0, 1, 1, 1]
    seq = [int(t) for t in jax.random.randint(jax.random.PRNGKey(7), (24,), 0, 256)]
    for _ in range(16):           # greedy under the penalty the benchmark asks for
        row = ref.logits(params, sizes, seq)[-1:]
        row = ref.penalized(row, seq, len(seq), loadgen.REPEAT_PENALTY,
                            loadgen.REPEAT_LAST_N)
        seq.append(int(row[0].argmax()))
    records = [{"index": 0, "context": seq, "n_prompt": 24}]
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

    for switch in ({"rope_everywhere": True}, {"window": False},
                   {"router_post_attn": True}, {"round_to": "float8_e4m3fn"}):
        got = reference_check.check(Broken(**switch), params, sizes,
                                    cfg.vocab_size, spec["reference"], records)
        assert not got["agrees"], (switch, got)


def test_a_routers_tie_is_counted_and_not_judged():
    """`logits` carries each layer's gap between the router's k-th and
    (k+1)-th logit (over the row's rms); `margins` reads a position whose
    logits came through a gap under ROUTER_TIE in any layer as 0, and every
    other position as it is. The switch of the lower precision is a word."""
    import numpy as np

    import reference_controls

    _, ref = _reference()
    assert ref.ROUTER_TIE == 2.0 ** -7          # bfloat16's epsilon
    tokens = [1, 2, 3, 4, 5, 6, 7, 1, 2, 3]
    rows = np.zeros((10, 8), np.float32).view(ref.RoutedLogits)
    rows[:, 0] = 1.0                               # the reference prefers id 0
    rows.router_gap = np.full((10, 3), 0.5)
    rows.router_gap[6, 1] = ref.ROUTER_TIE / 2     # predicts position 7
    short, top = ref.margins(rows, tokens, 4)
    assert short.shape == (6,) and float(top.max()) == 1.0
    assert short.tolist() == [1.0, 1.0, 1.0, 0.0, 1.0, 1.0]
    assert ref.margins(rows, tokens, 4, tie=0)[0].tolist() == [1.0] * 6
    # a plain array (another family's logits) is judged everywhere
    assert ref.margins(np.asarray(rows), tokens, 4)[0].tolist() == [1.0] * 6
    assert reference_controls.parse_switch("round_to=float8_e4m3fn") == (
        "round_to=float8_e4m3fn", {"round_to": "float8_e4m3fn"})
    assert reference_controls.parse_switch("window=False")[1] == {"window": False}
    assert reference_controls.parse_switch("rope_everywhere")[1] == {
        "rope_everywhere": True}


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "8", "--trace", "1", "--rehearse",
         "--out-dir", str(tmp_path / "out")],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, done.stdout[-3000:]
    # the counter's reader gives a number; the two trace readers find
    # nothing to read in a CPU's trace (its events carry no HLO line)
    assert 0 < line["metrics"]["moe.experts_touched_pct"]["value"] <= 100
    assert "moe.time_pct" not in line["metrics"]
    assert "moe.expert_mem_roofline_pct" not in line["metrics"]
    assert line["metrics"]["engine.window_compiles"]["value"] == 0


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "tests", "data", "smallthinker_chat.json")) as f:
        run = json.load(f)
    run["config"] = harness.Cell(CELL).config
    return run


def test_the_readers_on_what_the_chip_recorded(recorded):
    cell = harness.Cell(CELL)
    got = {name: cell.reader(name).compute(recorded) for name in READERS}
    want = dict(recorded["read_on_the_chip"])
    # PR 33 charged a launch every expert held (91.9); since PR 52 the
    # share charges the experts the capture's launches touched: 43,412
    # over 147 launches = 295.3 of 768 a launch, so it reads 35.3
    import phases

    touched = phases.touched_per_launch(recorded)
    assert touched == pytest.approx(43_412 / 147)
    want["moe.expert_mem_roofline_pct"] *= touched / (12 * 64)
    for name in READERS:
        assert got[name] == pytest.approx(want[name], rel=1e-9), name
        assert 0 < got[name] <= 100
    import moe

    # the three grouped products of the verify program, and nothing of
    # attention or the head among them
    texts = [o["text"] for o in moe.expert_ops(recorded)]
    assert texts and not any("ragged_attention" in t or "151936" in t for t in texts)


def test_a_program_without_the_scopes_or_counters_reads_as_nothing(recorded):
    """The parent's trace and scrape, or a dense configuration's: every
    reader returns None and none raises."""
    cell = harness.Cell(CELL)
    dense = {**recorded, "config": harness.Cell("mistral7b.chat").config}
    bare = {**recorded, "trace": {}, "worker_before": "", "worker_after": "",
            "trace_counters": None}
    for name in READERS:
        assert cell.reader(name).compute(dense) is None
        assert cell.reader(name).compute(bare) is None
