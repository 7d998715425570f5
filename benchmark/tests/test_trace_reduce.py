"""trace_reduce on hand-made planes, and on a small trace recorded on a
TPU v5e (tests/data/tiny.xplane.pb; see its README)."""
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_idle_and_per_name_sums_on_hand_made_planes():
    ms = 1_000_000
    planes = {"devices": {"/device:TPU:0": {
        # a while loop that holds two operations, as the scan over layers does
        "ops": [(0, 3 * ms, "%while.1 = (s32[]) while(...)"),
                (0, 2 * ms, "%fusion.1 = bf16[16,5,4096]{2,0,1} fusion(...)"),
                (2 * ms, 3 * ms, "%ragged_attention.2 = bf16[16,8,20,128] custom-call(...)"),
                (6 * ms, 8 * ms, "%fusion.1 = bf16[16,5,4096]{2,0,1} fusion(...)"),
                (9 * ms, 10 * ms, "%copy.3 = bf16[8] copy(...)")],
        "modules": [(0, 3 * ms, "jit_verify_block_fn(1123)"),
                    (6 * ms, 8 * ms, "jit_verify_block_fn(1123)"),
                    (9 * ms, 10 * ms, "jit_prefill_fn(7456)")]}},
        "host": [(2 * ms, 7 * ms, "python3:fetch"), (3 * ms, 6 * ms, "python3:sample"),
                 (8 * ms, 9 * ms + 1, "python3:admit")]}
    r = trace_reduce.reduce(planes)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.006)            # [0,3] + [6,8] + [9,10]
    assert r["idle_pct"] == pytest.approx(40.0)
    fusion = r["ops"]["jit_verify_block_fn#1123/fusion.1"]
    assert fusion["seconds"] == pytest.approx(0.004) and fusion["count"] == 2
    loop = r["ops"]["jit_verify_block_fn#1123/while.1"]
    assert loop["seconds"] == pytest.approx(0.0)           # all of it is its body's
    assert loop["total_seconds"] == pytest.approx(0.003)
    assert r["ops"]["jit_prefill_fn#7456/copy.3"]["program"] == "jit_prefill_fn"
    assert sum(o["seconds"] for o in r["ops"].values()) == pytest.approx(r["busy_s"])
    assert r["programs"]["jit_verify_block_fn"] == {"seconds": pytest.approx(0.005), "count": 2}
    assert r["breakdown"]["device_ops"][0] == [
        "jit_verify_block_fn#1123/fusion.1", pytest.approx(0.004)]
    # the 3 ms gap is inside "sample" (the shortest cover); the 1 ms gap inside "admit"
    assert r["breakdown"]["idle_gaps"] == [["python3:sample", pytest.approx(0.003)],
                                           ["python3:admit", pytest.approx(0.001)]]


def test_readers_find_kernels_by_todays_names():
    import readers

    run = {"trace": {"ops": {
        "p/ragged_attention.8": {"seconds": 1.0, "count": 2, "text":
                                 "%ragged_attention.8 = bf16[16,8,20,128]{3,2,1,0} custom-call(s32[4]{0} %x)"},
        "p/vmap__.9": {"seconds": 2.0, "count": 20, "text":
                       "%vmap__.9 = bf16[1024,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} custom-call(s32[1,2]{1,0} %y)"},
        "p/fusion.1": {"seconds": 4.0, "count": 1, "text": "%fusion.1 = bf16[1,512,4096]{2,1,0} fusion(...)"}}}}
    assert [o["key"] for o in readers.ops(run, readers.RAGGED_OPS)] == ["p/ragged_attention.8"]
    assert [o["key"] for o in readers.ops(run, readers.FLASH_OPS)] == ["p/vmap__.9"]


def test_nothing_to_read_returns_nothing():
    assert trace_reduce.reduce({"devices": {}, "host": []}) == {}


def test_recorded_tpu_trace():
    path = os.path.join(DATA, "tiny.xplane.pb")
    assert os.path.getsize(path) < 1_000_000
    r = trace_reduce.reduce(trace_reduce.read(path))
    assert list(r["devices"]) == ["/device:TPU:0"]
    # four launches each of two jitted programs (tests/data/README)
    assert r["programs"]["jit_step_a"]["count"] == 4
    assert r["programs"]["jit_step_b"]["count"] == 4
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_pct"] == pytest.approx(100 * (1 - r["busy_s"] / r["window_s"]))
    assert sum(o["seconds"] for o in r["ops"].values()) == pytest.approx(r["busy_s"], rel=1e-3)
    assert len(r["breakdown"]["device_ops"]) <= 10


COLLECTIVES = r"^%(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"


def test_recorded_four_chip_trace():
    """tests/data/tiny4.xplane.pb (``record_tiny4.py`` beside it): four
    device planes; busy and idle are the mean over the chips; programs and
    operations are the first chip's, not four times over; the sharded
    matmul's all-reduce is among the operations."""
    import re

    path = os.path.join(DATA, "tiny4.xplane.pb")
    assert os.path.getsize(path) < 1_000_000
    planes = trace_reduce.read(path)
    r = trace_reduce.reduce(planes)
    assert list(r["devices"]) == [f"/device:TPU:{i}" for i in range(4)]
    per_chip = list(r["devices"].values())
    assert r["idle_pct"] == pytest.approx(sum(d["idle_pct"] for d in per_chip) / 4)
    assert r["busy_s"] == pytest.approx(sum(d["busy_s"] for d in per_chip) / 4)
    assert r["idle_pct_max"] == max(d["idle_pct"] for d in per_chip)
    assert len({round(d["busy_s"], 9) for d in per_chip}) > 1      # four clocks, not one copied
    # four launches each, as the first chip saw them (all chips: sixteen)
    assert r["programs"]["jit_step_a"]["count"] == 4
    assert r["programs"]["jit_step_b"]["count"] == 4
    first = planes["devices"]["/device:TPU:0"]
    assert sum(o["count"] for o in r["ops"].values()) == len(first["ops"])
    assert sum(o["seconds"] for o in r["ops"].values()) == pytest.approx(
        per_chip[0]["busy_s"], rel=1e-3)
    collectives = [k for k, o in r["ops"].items() if re.search(COLLECTIVES, o["text"])]
    assert collectives and all(r["ops"][k]["program"] == "jit_step_a" for k in collectives)
    assert all(r["ops"][k]["count"] == 4 for k in collectives)
