"""The three readers PR 27 added: ``collective.time_pct`` on the recorded
four-chip trace (synchronous collectives, as the v5e shows them) and on a
hand-made one with an asynchronous pair; ``engine.load_s`` and
``engine.window_compiles`` on ``/metrics`` text; each gives nothing, and
raises nothing, where the program or the configuration has nothing to read.
And the four-chip cell rehearsed end to end over four host devices."""
import json
import os
import subprocess
import sys

import pytest

import run as harness
import trace_reduce
from conftest import BENCH, FOUR_DEVICES, ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "nemo12b-tp4.chat"


def reader(name: str):
    return harness.Cell(CELL).reader(name)


def op(text: str, seconds: float) -> dict:
    return {"text": text, "seconds": seconds, "total_seconds": seconds,
            "count": 1, "program": "jit_verify_block_fn"}


def test_collective_share_of_the_recorded_four_chip_trace():
    cell = harness.Cell(CELL)
    trace = trace_reduce.reduce(trace_reduce.read(os.path.join(DATA, "tiny4.xplane.pb")))
    first = trace["devices"]["/device:TPU:0"]["busy_s"]
    mine = [o for o in trace["ops"].values() if " all-reduce(" in o["text"]]
    assert mine and all(o["text"].startswith("%all-reduce") for o in mine)
    want = 100.0 * sum(o["seconds"] for o in mine) / first
    got = cell.reader("collective.time_pct").compute(
        {"trace": trace, "config": cell.config})
    assert got == pytest.approx(want) and 0.0 < got < 100.0
    # the same trace under a configuration with no mesh: nothing, not 0
    one_chip = harness.Cell("mistral7b.chat")
    assert reader("collective.time_pct").compute(
        {"trace": trace, "config": one_chip.config}) is None
    assert CELL in [m for m in cell.manifest["per_layer"]
                    if m["name"] == "collective.time_pct"][0]["workloads"]
    assert "collective.time_pct" not in one_chip.metric_names("per_layer")


def test_an_asynchronous_pair_counts_once_by_its_done():
    cfg = harness.Cell(CELL).config
    ops = {
        "v/a": op("%all-reduce.7 = bf16[16,5,5120]{2,1,0} all-reduce(bf16[16,5,5120] %dot.3), replica_groups={{0,1,2,3}}", 0.010),
        "v/b": op("%all-gather-start.2 = (f32[16,32768], f32[16,131072]) all-gather-start(f32[16,32768] %x), dimensions={1}", 0.001),
        "v/c": op("%all-gather-done.2 = f32[16,131072]{1,0} all-gather-done((f32[16,32768], f32[16,131072]) %all-gather-start.2)", 0.004),
        "v/d": op("%collective-permute-done.1 = bf16[8,128] collective-permute-done(%collective-permute-start.1)", 0.002),
        "v/e": op("%reduce-scatter.4 = bf16[4,5120] reduce-scatter(bf16[16,5120] %y), dimensions={0}", 0.003),
        "v/f": op("%all-to-all.9 = bf16[4,64] all-to-all(bf16[4,64] %z), dimensions={0}", 0.001),
        # not collectives: a fusion that reduces, a kernel, a name that only looks like one
        "v/g": op("%fusion.12 = bf16[16,5120] fusion(bf16[16,14336] %h), kind=kOutput, calls=%fused_reduce", 0.050),
        "v/h": op("%ragged_attention.8 = bf16[16,2,20,128]{3,2,1,0} custom-call(s32[4]{0} %x)", 0.020),
        "v/i": op("%all-reduce-scatter_fusion = bf16[4] fusion(bf16[16] %w), kind=kLoop", 0.009),
    }
    run = {"config": cfg, "trace": {
        "ops": ops, "devices": {"/device:TPU:0": {"busy_s": 0.100},
                                "/device:TPU:1": {"busy_s": 0.050}}}}
    # 10 + 4 (the done, not the start) + 2 + 3 + 1 ms of the first chip's 100
    assert reader("collective.time_pct").compute(run) == pytest.approx(20.0)
    assert reader("collective.time_pct").compute({"config": cfg, "trace": {}}) is None
    assert reader("collective.time_pct").compute({"config": cfg}) is None


LOAD = ('gridllm_model_load_seconds_bucket{{model="m",source="init",le="+Inf"}} 1\n'
        'gridllm_model_load_seconds_sum{{model="m",source="init"}} {a}\n'
        'gridllm_model_load_seconds_count{{model="m",source="init"}} 1\n'
        'gridllm_model_load_seconds_sum{{model="e",source="checkpoint"}} {b}\n'
        'gridllm_model_load_seconds_count{{model="e",source="checkpoint"}} 1\n')
COMPILES = ('gridllm_xla_compile_seconds_sum{{model="m"}} 41.5\n'
            'gridllm_xla_compile_seconds_count{{model="m"}} {m}\n'
            'gridllm_xla_compile_seconds_count{{model=""}} {none}\n')


def test_load_seconds_and_window_compiles():
    before = LOAD.format(a=12.5, b=0.25) + COMPILES.format(m=60, none=3)
    sound = {"worker_before": before, "worker_after": before}
    assert reader("engine.load_s").compute(sound) == pytest.approx(12.75)
    assert reader("engine.window_compiles").compute(sound) == 0.0
    # two executables built under traffic, one by a thread no engine owns
    after = LOAD.format(a=12.5, b=0.25) + COMPILES.format(m=61, none=4)
    assert reader("engine.window_compiles").compute(
        {"worker_before": before, "worker_after": after}) == 2.0
    # the parent of the PR that added the counter, and a worker with no engine
    parent = {"worker_before": LOAD.format(a=1.0, b=0.0),
              "worker_after": LOAD.format(a=1.0, b=0.0)}
    assert reader("engine.window_compiles").compute(parent) is None
    assert reader("engine.load_s").compute(parent) == pytest.approx(1.0)
    empty = {"worker_before": "", "worker_after": ""}
    assert reader("engine.load_s").compute(empty) is None
    assert reader("engine.window_compiles").compute(empty) is None
    # both are reported in every cell, the one-chip ones too
    for cell in ("mistral7b.chat", "mistral7b.shared_doc", CELL):
        names = harness.Cell(cell).metric_names("per_layer")
        assert {"engine.load_s", "engine.window_compiles"} <= set(names)


def test_the_four_chip_cell_is_mistral_nemo_with_nothing_reduced():
    import dataclasses

    import launch_worker
    from gridllm_tpu.models.configs import get_config

    cell = harness.Cell(CELL)
    assert (cell.chips, cell.config["mesh"], cell.config["reduced"]) == (4, "tp:4", {})
    got = launch_worker.model_config(cell.config, cell.config_name, False)
    assert got == dataclasses.replace(get_config("mistral-nemo:12b"),
                                      name=cell.config_name)
    assert cell.config["env"] == harness.Cell("mistral7b.chat").config["env"]
    assert cell.config["reference"]["module"] == "reference/llama_f32.py"
    import costs

    assert costs.chip_share(cell.config) == {"weights": 4, "kv": 4, "heads": 4}
    assert costs.total_params(cell.config) == 12_247_782_400
    assert costs.kv_bytes_per_token(cell.config) // 4 == 40_960
    env = harness.deployment_env(cell.config, rehearse=True)
    assert env["GRIDLLM_MESH_SHAPE"] == "tp:4" and env["XLA_FLAGS"] == FOUR_DEVICES
    four = [w for w in cell.manifest["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [CELL]


def test_the_four_chip_cell_rehearses_on_four_host_devices(tmp_path):
    """``run.py --rehearse`` of the cell: tiny-nemo under ``tp:4`` through
    the three-process path, kernels interpreted, the reference check on the
    sharded tree, and nothing built by jax inside the window."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483999", "--seconds", "6", "--trace", "1", "--rehearse",
         "--out-dir", str(tmp_path / "out")],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["device"] == {
        "platform": "cpu", "kind": "cpu", "count": 4, "memory_peak_bytes": 0}
    assert line["correct"], done.stdout[-3000:]
    m = line["metrics"]
    assert m["engine.window_compiles"]["value"] == 0.0
    assert m["engine.load_s"]["value"] > 0.0
    assert "collective.time_pct" not in m          # no device number from a CPU
    assert "collective.time_pct reader returned" in done.stdout
    worker = (tmp_path / "out" / "worker.log").read_text()
    ready = next(x for x in worker.splitlines() if "weights ready" in x)
    rec = json.loads(ready[ready.index("{"):])
    assert rec["mesh"] == "tp:4" and rec["devices"] == 4 and rec["source"] == "init"
    assert rec["initCompileS"] > 0 and rec["paramBytesMaxDevice"] > 0
