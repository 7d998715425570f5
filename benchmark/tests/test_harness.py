"""A cell, a configuration, a traffic mix and a layer metric dropped in as
new files (plus new BENCHMARK.json entries) are found by name, with no edit
to a file that was there; and BENCHMARK.json agrees with the files."""
import dataclasses
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import costs
import launch_worker
import run as harness
import trafficgen
from conftest import BENCH, FOUR_DEVICES, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def manifest():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "benchmark"
    for sub in ("configs", "workloads", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    m = manifest()
    # a later PR's additions: four new files ...
    cfg = json.loads((bench / "configs" / "mistral-7b-v0.3-L20.json").read_text())
    cfg["num_hidden_layers"] = 12
    (bench / "configs" / "mistral-7b-v0.3-L12.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "chat_burst.json").write_text(json.dumps({
        "streams": json.loads((bench / "traffic" / "chat.json").read_text())["streams"],
        "bursts": [{"seconds": 5, "factor": 2.0}, {"seconds": 5, "factor": 0.4}]}))
    (bench / "workloads" / "mistral7b-L12.chat_burst.json").write_text(
        json.dumps({"rate": 7.5}))
    (bench / "layer_metrics" / "gen.sent_count.py").write_text(
        'NAME, UNIT, LAYER, MOVES = "gen.sent_count", "requests", '
        '"load generator", "out_tok_s"\nCELLS = ["mistral7b-L12.chat_burst"]\n\n'
        "def compute(run):\n    return float(len(run['outcomes']))\n")
    # ... and four new entries
    m["configs"].append({"name": "mistral-7b-v0.3-L12", "source": cfg["source"],
                         "file": "benchmark/configs/mistral-7b-v0.3-L12.json",
                         "reduced": ["num_hidden_layers"], "why": "test"})
    m["workloads"].append({"name": "mistral7b-L12.chat_burst",
                           "config": "mistral-7b-v0.3-L12",
                           "traffic": "chat_burst", "chips": 1, "why": "test"})
    m["per_layer"].append({"name": "gen.sent_count", "unit": "requests",
                           "better": "higher", "source": "program_counter",
                           "layer": "load generator", "moves": "out_tok_s",
                           "workloads": ["mistral7b-L12.chat_burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    cell = harness.Cell("mistral7b-L12.chat_burst", bench_dir=str(bench))
    assert cell.config["num_hidden_layers"] == 12 and cell.rate == 7.5
    assert cell.config_file.endswith("mistral-7b-v0.3-L12.json")
    reqs = trafficgen.generate(cell.mix, cell.rate, 40, 1)
    assert len(reqs) == 300
    assert "gen.sent_count" in cell.metric_names("per_layer")
    assert cell.reader("gen.sent_count").compute({"outcomes": reqs}) == 300.0
    # the old cell does not report the new cell's metric, and still resolves
    old = harness.Cell("mistral7b.chat", bench_dir=str(bench))
    assert "gen.sent_count" not in old.metric_names("per_layer")
    assert old.config["num_hidden_layers"] == 20
    assert {p: p.read_bytes() for p in before} == before     # nothing edited


def test_manifest_agrees_with_the_files():
    m = manifest()
    e2e = {x["name"] for x in m["end_to_end"]}
    assert {"ttft_p50_ms", "ttft_p85_ms", "ttft_p95_ms", "itl_p95_ms", "out_tok_s",
            "setup_s"} == e2e
    for x in m["end_to_end"]:
        assert 0 < x["bound"] <= 0.1 and x["source"] in ("host_clock", "device_trace")
    for w in m["workloads"]:
        cell = harness.Cell(w["name"])
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and cell.rate > 0
        assert trafficgen.generate(cell.mix, cell.rate, m["run_seconds"], 1)
        for name in cell.metric_names("per_layer"):
            mod, entry = cell.reader(name), next(
                x for x in m["per_layer"] if x["name"] == name)
            assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
                name, entry["unit"], entry["layer"], entry["moves"])
            # the metric it should move is reported in this cell; an entry
            # with no `workloads` key is owed where that metric is reported
            # and is read in the other cells too (ttft_p50_ms is judged in
            # five cells of eight: PERF.md section 2)
            assert NAME.match(name) and entry["moves"] in e2e
            assert (entry["moves"] in cell.metric_names("end_to_end")
                    or "workloads" not in entry)
        reported = cell.metric_names("end_to_end")
        assert "setup_s" in reported and len(reported) >= 2
        assert set(reported) <= set(harness.stats.end_to_end([harness.loadgen.Outcome(0, 0.0)], 0.0, 1.0, 1.0)) | {"setup_s"}
    for c in m["configs"]:
        spec = json.load(open(os.path.join(os.path.dirname(BENCH), c["file"])))
        assert set(c["reduced"]) == set(spec["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])


def test_warm_up_touches_this_cells_shapes_only():
    cell = harness.Cell("mistral7b.chat")
    reqs = trafficgen.generate(cell.mix, 5.0, 40, 1)
    first, second = harness.warmup_requests(reqs, 16, 1024, [512, 1024, 2048], 1)
    sizes = sorted(len(r.prompt) for r in first)
    assert sizes[-3:] == [504, 1016, 2048] and len(first) == 16   # never more than the slots
    assert [len(r.prompt) for r in second] == [1016, 2048]
    doc = harness.Cell("mistral7b.shared_doc")
    reqs = trafficgen.generate(doc.mix, 4.5, 40, 1)
    first, second = harness.warmup_requests(reqs, 16, 1024, [512, 1024, 2048], 1)
    assert max(len(r.prompt) for r in first) == 4096 + 64
    assert not any(len(r.prompt) in (504, 1016) for r in first)  # no bucket it never reaches
    assert first[-1].prompt[:4096] == second[-1].prompt[:4096]   # the prefix hit


def test_the_reference_sample_takes_a_cold_and_a_cached_question():
    doc = harness.Cell("mistral7b.shared_doc")
    reqs = trafficgen.generate(doc.mix, 4.5, 40, 1)
    a, b = sorted(harness.reference_sample(reqs))
    assert reqs[a].group == reqs[b].group and reqs[a].prompt != reqs[b].prompt
    chat = trafficgen.generate(harness.Cell("mistral7b.chat").mix, 5.0, 40, 1)
    assert len(harness.reference_sample(chat)) == 2


# -- a meshed configuration, and a second family, with no edit ---------------

MOE_COSTS = '''"""A routed-expert decoder: a step reads the attention weights, the router
and, of the experts, those its tokens are routed to (at most all)."""
import costs as dense


def attn_params(spec):
    e, d = spec["hidden_size"], dense.head_dim(spec)
    h, kvh = spec["num_attention_heads"], spec["num_key_value_heads"]
    return e * h * d + 2 * e * kvh * d + h * d * e + 2 * e


def expert_params(spec):
    return 3 * spec["hidden_size"] * spec["intermediate_size"]


def total_params(spec):
    per_layer = (attn_params(spec) + spec["hidden_size"] * spec["num_local_experts"]
                 + spec["num_local_experts"] * expert_params(spec))
    return spec["num_hidden_layers"] * per_layer + dense.embedding_params(spec)


def weight_bytes(spec):
    return total_params(spec) * dense.DTYPE_BYTES[spec["dtype"]]


def step_weight_bytes(spec, rows=1):
    read = min(spec["num_local_experts"], rows * spec["num_experts_per_tok"])
    per_layer = (attn_params(spec) + spec["hidden_size"] * spec["num_local_experts"]
                 + read * expert_params(spec))
    head = spec["vocab_size"] * spec["hidden_size"]
    return (spec["num_hidden_layers"] * per_layer + head) * dense.DTYPE_BYTES[spec["dtype"]]


kv_bytes_per_token = dense.kv_bytes_per_token
flash_prefill_flops = dense.flash_prefill_flops


def chip_share(spec):
    """ep splits the experts, tp every projection and the KV heads."""
    axes = dense.mesh_axes(spec)
    tp, ep = axes.pop("tp", 1), axes.pop("ep", 1)
    if any(n > 1 for n in axes.values()):
        return None
    return {"weights": tp * ep, "kv": tp, "heads": tp}
'''

MOE_STUB = '''"""A stand-in for a second family's plain reference: it checks what the
harness handed it, and "predicts" token 7 t + 3 after t."""
import importlib.util
import os

import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "llama_f32", os.path.join(os.path.dirname(__file__), "llama_f32.py"))
_llama = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_llama)
margins = _llama.margins


def sizes(cfg):
    return {"experts": cfg.num_experts, "per_token": cfg.experts_per_token}


def logits(params, spec, tokens, skip_layer=None):
    assert spec == {"experts": 4, "per_token": 2}, spec       # sizes(cfg), not eight llama keys
    gate = params["layers"]["we_gate"]                         # the family module's tree
    assert gate.shape[:2] == (2, 4) and len(gate.devices()) == 4, gate.sharding
    assert gate.addressable_shards[0].data.shape == (2, 2, 64, 64)   # ep:2 x tp:2
    v = params["embed"].shape[0]
    nxt = (jnp.asarray(tokens) * 7 + 3 + (skip_layer is not None)) % v
    return jnp.zeros((len(tokens), v), jnp.float32).at[jnp.arange(len(tokens)), nxt].set(1.0)
'''


def copy_of_the_benchmark(tmp_path):
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("out", "__pycache__"))
    return bench, {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}


def child(bench, *argv: str) -> dict:
    """A benchmark program run from the copy, over four host devices."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": FOUR_DEVICES,
           "PYTHONPATH": ROOT + os.pathsep + str(bench)}
    done = subprocess.run([sys.executable, *argv], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    line = [x for x in done.stdout.splitlines() if x.startswith("REFERENCE=")][-1]
    return json.loads(line[len("REFERENCE="):])


def test_a_meshed_second_family_is_new_files_only(tmp_path, monkeypatch):
    """tiny-mixtral under ep:2 x tp:2 (the axes tests/test_parallel.py puts
    it under) over four host devices: its configuration, its costs file and
    its reference module are dropped into a copy and each is found by name;
    the weights come from the program's mixtral module, made sharded."""
    bench, before = copy_of_the_benchmark(tmp_path)
    m = manifest()
    (bench / "family_costs").mkdir()
    (bench / "family_costs" / "moe.py").write_text(MOE_COSTS)
    (bench / "reference" / "moe_stub.py").write_text(MOE_STUB)
    cfg = {"source": "test", "base": "tiny-mixtral", "rehearse_base": "tiny-mixtral",
           "model_type": "mixtral", "vocab_size": 256, "hidden_size": 64,
           "intermediate_size": 128, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
           "rope_theta": 10000.0, "max_position_embeddings": 256,
           "num_local_experts": 4, "num_experts_per_tok": 2, "reduced": {},
           "chips": 4, "mesh": "ep:2,tp:2", "dtype": "float32",
           "costs": "family_costs/moe.py", "env": {"GRIDLLM_MAX_BATCH_SLOTS": "4"},
           "rehearse_env": {"XLA_FLAGS": FOUR_DEVICES},
           "reference": {"module": "reference/moe_stub.py", "margin_abs": 0.05,
                         "margin_rel": 0.01, "margin_mean": 0.01,
                         "timeout_s": 900}}
    (bench / "configs" / "tiny-mixtral-ep2tp2.json").write_text(json.dumps(cfg))
    (bench / "workloads" / "tiny-mixtral.chat.json").write_text(json.dumps({"rate": 2.0}))
    m["configs"].append({"name": "tiny-mixtral-ep2tp2", "source": "test",
                         "file": "benchmark/configs/tiny-mixtral-ep2tp2.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "tiny-mixtral.chat", "config": "tiny-mixtral-ep2tp2",
                           "traffic": "chat", "chips": 4, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    monkeypatch.setattr(costs, "HERE", str(bench))   # costs.py as the copy's would look
    cell = harness.Cell("tiny-mixtral.chat", bench_dir=str(bench))
    assert (cell.chips, cell.config["mesh"]) == (4, "ep:2,tp:2")
    # the program's reader keeps the keys a table of nine dense ones dropped
    from gridllm_tpu.models.configs import get_config

    got = launch_worker.model_config(cell.config, cell.config_name, False)
    assert got == dataclasses.replace(get_config("tiny-mixtral"), name=cell.config_name)
    assert (got.family, got.num_experts, got.experts_per_token) == ("mixtral", 4, 2)
    # one source of the mesh, whatever the caller's environment says
    monkeypatch.setenv("GRIDLLM_MESH_SHAPE", "tp:8")
    env = harness.deployment_env(cell.config, rehearse=True)
    assert env["GRIDLLM_MESH_SHAPE"] == "ep:2,tp:2" and env["XLA_FLAGS"] == FOUR_DEVICES
    assert harness.deployment_env(harness.Cell("mistral7b.chat").config, False) == {
        "GRIDLLM_MAX_BATCH_SLOTS": "16", "RATE_LIMIT_MAX_REQUESTS": "1000000",
        "GRIDLLM_MESH_SHAPE": ""}
    assert cell.config["reference"]["timeout_s"] == 900
    # its costs, by name: a step reads 2 of 4 experts, a chip a quarter of that
    mine = costs.of(cell.config)
    assert mine.__file__ == str(bench / "family_costs" / "moe.py")
    assert mine.step_weight_bytes(cell.config) < mine.weight_bytes(cell.config)
    assert mine.chip_share(cell.config) == {"weights": 4, "kv": 2, "heads": 2}
    assert costs.chip_share(cell.config) is None            # the dense file has no rule for ep
    # the reference check, run from the copy: the stub is found by name, is
    # handed its own sizes(cfg) and the mixtral tree sharded over the mesh
    toks = [5]
    for _ in range(11):
        toks.append((toks[-1] * 7 + 3) % 256)
    (tmp_path / "records.json").write_text(json.dumps(
        [{"index": 0, "context": toks, "n_prompt": 4}]))
    out = child(bench, str(bench / "reference_check.py"), "--config",
                cell.config_file, "--records", str(tmp_path / "records.json"),
                "--rehearse", "--control")
    assert out["agrees"] and out["layer_skipped_fails"] and out["devices"] == 4
    assert {p: p.read_bytes() for p in before} == before     # nothing edited


OLD_KEYS = {        # launch_worker.HF_KEYS as it stood before this PR
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings", "max_position_embeddings": "max_seq_len"}


def test_every_committed_configuration_reads_as_it_did():
    from gridllm_tpu.models.configs import get_config

    for c in manifest()["configs"]:
        spec = json.load(open(os.path.join(ROOT, c["file"])))
        sizes = {field: spec[key] for key, field in OLD_KEYS.items() if key in spec}
        sizes["sliding_window"] = spec.get("sliding_window") or 0
        was = dataclasses.replace(get_config(spec["base"]), name=c["name"], **sizes)
        assert launch_worker.model_config(spec, c["name"], False) == was
        launch_worker.check_deployment(spec, c["name"])


def test_an_unlisted_difference_is_refused():
    spec = harness.Cell("mistral7b.chat").config
    for change in ({"hidden_size": 2048}, {"num_local_experts": 8},
                   {"model_type": "qwen2"}, {"sliding_window": 4096},
                   {"reduced": {}}):
        with pytest.raises(SystemExit, match="not listed under reduced"):
            launch_worker.model_config({**spec, **change}, "x", False)
    # listed by its published key, or (no published key) by its field's name
    listed = {**spec, "model_type": "qwen2", "reduced": {
        **spec["reduced"], "family": {"from": "llama", "to": "qwen2"},
        "attn_bias": {"from": False, "to": True}}}
    assert launch_worker.model_config(listed, "x", False).family == "qwen2"
    # a `from` that is not the registry's is no licence
    wrong = {**spec, "reduced": {"num_hidden_layers": {"from": 40, "to": 20}}}
    with pytest.raises(SystemExit, match="num_layers=40"):
        launch_worker.model_config(wrong, "x", False)


@pytest.mark.parametrize("change,said", [
    ({"mesh": "tp:4"}, "spans 4 chips but chips is 1"),
    ({"chips": 4}, "spans 1 chips but chips is 4"),
    ({"mesh": "tp:4", "chips": 4, "env": {"GRIDLLM_MESH_SHAPE": "tp:2"}}, "env sets"),
    ({"env": {"GRIDLLM_MESH_SHAPE": "tp:4"}}, "env sets"),
    ({"mesh": "tp:3", "chips": 3}, "KV heads"),
    ({"mesh": "xx:4", "chips": 4}, "is not <axis>:<size>"),
])
def test_a_deployment_that_disagrees_with_its_mesh_is_refused(change, said):
    spec = harness.Cell("mistral7b.chat").config
    with pytest.raises(SystemExit, match=said):
        launch_worker.check_deployment({**spec, **change}, "x")
    launch_worker.check_deployment({**spec, "mesh": "tp:4", "chips": 4}, "x")


def test_the_reference_check_rehearses_under_tp4(tmp_path):
    """The committed configuration with ``mesh: "tp:4"`` and nothing else
    changed, rehearsed over four host devices: ``reference/llama_f32.py``,
    unedited, runs on the tp-sharded tiny-mistral tree, agrees with tokens
    it chose itself on the unsharded one, and its skipped layer fails."""
    import importlib.util

    import jax
    import jax.numpy as jnp

    import loadgen
    from gridllm_tpu.models import llama
    from gridllm_tpu.models.configs import get_config

    spec = harness.Cell("mistral7b.chat").config
    scratch = {**spec, "mesh": "tp:4", "chips": 4,
               "rehearse_env": {**spec["rehearse_env"], "XLA_FLAGS": FOUR_DEVICES}}
    (tmp_path / "mistral-7b-v0.3-L20-tp4.json").write_text(json.dumps(scratch))
    mod_spec = importlib.util.spec_from_file_location(
        "llama_f32_t", os.path.join(BENCH, spec["reference"]["module"]))
    ref = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(ref)
    cfg = get_config(spec["rehearse_base"])
    params = llama.init_params(cfg, jax.random.PRNGKey(0), getattr(jnp, spec["dtype"]))
    import reference_check

    sizes = reference_check.reference_sizes(ref, cfg, spec, rehearse=True)
    seq = [int(t) for t in jax.random.randint(jax.random.PRNGKey(7), (16,), 0, 256)]
    for _ in range(16):           # greedy under the penalty the benchmark asks for
        row = ref.logits(params, sizes, seq)[-1:]
        row = ref.penalized(row, seq, len(seq), loadgen.REPEAT_PENALTY,
                            loadgen.REPEAT_LAST_N)
        seq.append(int(row[0].argmax()))
    (tmp_path / "records.json").write_text(json.dumps(
        [{"index": 0, "context": seq, "n_prompt": 16}]))
    out = child(BENCH, os.path.join(BENCH, "reference_check.py"), "--config",
                str(tmp_path / "mistral-7b-v0.3-L20-tp4.json"), "--records",
                str(tmp_path / "records.json"), "--rehearse", "--control")
    assert out["devices"] == 4 and out["agrees"], out
    assert out["records"][0]["worst_shortfall"] < 1e-4       # the same model, sharded
    assert out["layer_skipped_fails"], out["layer_skipped"]
