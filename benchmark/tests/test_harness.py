"""A cell, a configuration, a traffic mix and a layer metric dropped in as
new files (plus new BENCHMARK.json entries) are found by name, with no edit
to a file that was there; and BENCHMARK.json agrees with the files."""
import json
import os
import re
import shutil


import run as harness
import trafficgen
from conftest import BENCH

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
    assert {"ttft_p50_ms", "ttft_p90_ms", "ttft_p95_ms", "itl_p95_ms", "out_tok_s",
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
            # the metric it should move is reported in this cell
            assert entry["moves"] in cell.metric_names("end_to_end") and NAME.match(name)
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
