"""The eleven readers of ISSUE 59 (the stages of an admission, of a fetch
and of an ingest; the unfed clock and idle_wait as shares of the runner's
wall; the collector's pauses and the worker loop's lag), each over two
hand-written ``/metrics`` texts: values to the digit, nothing (and no
error) on a text without the series, the two ``*_max_ms`` 0 where no
bucket rose, and every new ``per_layer`` entry has its file."""
import json
import os

import pytest

import phases
import run as harness
import stages
from conftest import BENCH, ROOT

RUNNER, ADMISSION = "engine runner (host loop)", "engine admission"
# name: (unit, source, layer, moves)
NEW = {
    "admit.tokenize_ms_per_request": ("ms", "program_span", ADMISSION, "itl_p95_ms"),
    "admit.match_ms_per_request": ("ms", "program_span", ADMISSION, "itl_p95_ms"),
    "admit.seed_ms_per_request": ("ms", "program_span", ADMISSION, "itl_p95_ms"),
    "admit.chunk_call_ms_per_launch": ("ms", "program_span", ADMISSION, "itl_p95_ms"),
    "fetch.copy_ms_per_step": ("ms", "program_span", RUNNER, "itl_p95_ms"),
    "ingest.emit_ms_per_step": ("ms", "program_span", RUNNER, "itl_p95_ms"),
    "runner.unfed_pct": ("%", "program_counter", RUNNER, "itl_p95_ms"),
    "runner.no_work_pct": ("%", "program_counter", RUNNER, "out_tok_s"),
    "host.gc_pause_ms_per_s": ("ms/s", "program_counter", RUNNER, "itl_p95_ms"),
    "host.gc_pause_max_ms": ("ms", "program_counter", RUNNER, "itl_p95_ms"),
    "worker.loop_lag_max_ms": ("ms", "program_counter", "HTTP API / worker",
                               "itl_p95_ms"),
}

# phase: (wall s, stretches) when the window opens and after it: 50
# admissions, 1,000 launches, a runner's wall of 40 s in the window
PHASES0 = {"idle_wait": (10.0, 20), "ctl": (0.10, 100), "admit": (0.20, 10),
           "dispatch_prefill": (0.50, 10), "draft": (0.05, 100),
           "dispatch_verify": (0.40, 100), "fetch": (2.00, 100),
           "ingest": (0.30, 100)}
PHASES1 = {"idle_wait": (14.0, 24), "ctl": (0.60, 1100), "admit": (0.70, 60),
           "dispatch_prefill": (2.50, 60), "draft": (0.25, 1100),
           "dispatch_verify": (2.40, 1100), "fetch": (32.00, 1100),
           "ingest": (1.10, 1100)}
STAGES0 = {("admit", "tokenize"): (0.10, 10), ("admit", "match"): (0.05, 10),
           ("dispatch_prefill", "seed"): (0.10, 10),
           ("dispatch_prefill", "chunk"): (0.30, 20),
           ("dispatch_prefill", "book"): (0.05, 10),
           ("fetch", "wait"): (1.80, 100), ("fetch", "copy"): (0.20, 100),
           ("ingest", "emit"): (0.10, 400)}
STAGES1 = {("admit", "tokenize"): (0.40, 60), ("admit", "match"): (0.15, 60),
           ("dispatch_prefill", "seed"): (0.60, 60),
           ("dispatch_prefill", "chunk"): (1.50, 170),
           ("dispatch_prefill", "book"): (0.30, 60),
           ("fetch", "wait"): (29.80, 1100), ("fetch", "copy"): (2.20, 1100),
           ("ingest", "emit"): (0.50, 4400)}
EDGES = (0.0002, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
         0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0)


def hist(name: str, labels: str, seen: list[float]) -> list[str]:
    """A Prometheus histogram's lines over ``EDGES`` for observations
    `seen` (seconds each)."""
    sep = "," if labels else ""
    lines = [f'{name}_bucket{{{labels}{sep}le="{ub:g}"}} '
             f"{sum(v <= ub for v in seen)}" for ub in EDGES]
    lines.append(f'{name}_bucket{{{labels}{sep}le="+Inf"}} {len(seen)}')
    tail = f"{{{labels}}}" if labels else ""
    return lines + [f"{name}_sum{tail} {sum(seen)}",
                    f"{name}_count{tail} {len(seen)}"]


def text(phases_: dict, stages_: dict | None = None, unfed: float | None = None,
         gc: dict[int, list[float]] | None = None,
         lag: list[float] | None = None) -> str:
    lines = []
    for phase, (wall, n) in phases_.items():
        lab = f'{{model="m",phase="{phase}"}}'
        lines += [f"gridllm_engine_phase_seconds_sum{lab} {wall}",
                  f"gridllm_engine_phase_seconds_count{lab} {n}"]
    for (phase, stage), (wall, n) in (stages_ or {}).items():
        lab = f'{{model="m",phase="{phase}",stage="{stage}"}}'
        lines += [f"gridllm_engine_stage_seconds_sum{lab} {wall}",
                  f"gridllm_engine_stage_seconds_count{lab} {n}"]
    if unfed is not None:
        lines.append(f'gridllm_engine_unfed_seconds_total{{model="m"}} {unfed}')
    for generation, seen in (gc or {}).items():
        lines += hist("gridllm_process_gc_pause_seconds",
                      f'generation="{generation}"', seen)
    if lag is not None:
        lines += hist("gridllm_worker_loop_lag_seconds", "", lag)
    return "\n".join(lines) + "\n"


GC0 = {0: [0.0001] * 50, 2: [0.3]}
GC1 = {0: [0.0001] * 150 + [0.004], 1: [0.002] * 5, 2: [0.3, 0.08]}
LAG0 = [0.0001] * 100
LAG1 = [0.0001] * 900 + [0.03]


def a_run() -> dict:
    return {"worker_before": text(PHASES0, STAGES0, 1.0, GC0, LAG0),
            "worker_after": text(PHASES1, STAGES1, 7.0, GC1, LAG1)}


def the_parent() -> dict:
    """The phase series alone: the program before the PR."""
    return {"worker_before": text(PHASES0), "worker_after": text(PHASES1)}


def read(name: str, run: dict):
    return harness.Cell("mistral7b.chat").reader(name).compute(run)


WANT = {
    "admit.tokenize_ms_per_request": 6.0,       # 0.30 s over 50 admissions
    "admit.match_ms_per_request": 2.0,
    "admit.seed_ms_per_request": 10.0,          # 0.50 s over admit's 50
    "admit.chunk_call_ms_per_launch": 8.0,      # 1.20 s over 150 stretches
    "fetch.copy_ms_per_step": 2.0,              # 2.00 s over 1,000 launches
    "ingest.emit_ms_per_step": 0.4,
    "runner.unfed_pct": 15.0,                   # 6 s of a wall of 40 s
    "runner.no_work_pct": 10.0,                 # 4 s of idle_wait
    # 0.01 + 0.004 + 0.01 + 0.08 s of pauses in 40 s
    "host.gc_pause_ms_per_s": 2.6,
    "host.gc_pause_max_ms": 100.0,              # 0.08 s: the bucket up to 0.1
    "worker.loop_lag_max_ms": 50.0,             # 0.03 s: the bucket up to 0.05
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_readers_to_the_digit(name):
    assert read(name, a_run()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_and_no_error_on_a_program_without_the_series(name):
    """The parent serves the phases alone: `runner.no_work_pct` reads, the
    other ten say nothing; a text with nothing at all gives nothing."""
    got = read(name, the_parent())
    if name == "runner.no_work_pct":
        assert got == pytest.approx(10.0)
    else:
        assert got is None
    assert read(name, {"worker_before": "", "worker_after": ""}) is None


@pytest.mark.parametrize("name, series", [
    ("host.gc_pause_max_ms", "gc"), ("worker.loop_lag_max_ms", "lag")])
def test_the_longest_reads_0_where_no_bucket_rose(name, series):
    same = {"worker_before": text(PHASES0, STAGES0, 1.0, GC0, LAG0),
            "worker_after": text(PHASES1, STAGES1, 7.0, GC0, LAG0)}
    assert read(name, same) == 0.0
    if series == "gc":
        assert read("host.gc_pause_ms_per_s", same) == 0.0


def test_an_observation_past_the_last_edge_reads_as_that_edge():
    run = a_run()
    run["worker_after"] = text(PHASES1, STAGES1, 7.0,
                               {**GC1, 2: [0.3, 0.08, 90.0]}, LAG1)
    assert read("host.gc_pause_max_ms", run) == pytest.approx(60e3)


def test_a_stage_new_in_the_window_counts_from_zero():
    """A stage first observed inside the window (no sample before it)."""
    run = a_run()
    run["worker_before"] = text(PHASES0, {}, 1.0, GC0, LAG0)
    assert stages.window(run)["fetch", "copy"] == (2.20, 1100)
    assert read("admit.match_ms_per_request", run) == pytest.approx(0.15e3 / 60)


def test_stages_and_their_unstaged_head_make_the_phase():
    """What PERF.md's split of an admission rests on: Σ stages <= phase."""
    w, p = stages.window(a_run()), phases.window(a_run())
    for phase in ("admit", "dispatch_prefill", "fetch", "ingest"):
        staged = sum(s for (ph, _), (s, _) in w.items() if ph == phase)
        assert staged <= p[phase][0] + 1e-9
    assert w["fetch", "wait"][0] + w["fetch", "copy"][0] == pytest.approx(
        p["fetch"][0])


def test_every_new_entry_has_its_file_and_the_file_says_what_the_entry_says():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-len(NEW):] == list(NEW)           # appended, in order
    entries = {m["name"]: m for m in manifest["per_layer"] if m["name"] in NEW}
    cell = harness.Cell("mistral7b.chat")
    for name, (unit, source, layer, moves) in NEW.items():
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
        mod, e = cell.reader(name), entries[name]
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            name, e["unit"], e["layer"], e["moves"])
        assert (e["unit"], e["better"], e["source"], e["layer"], e["moves"]) == (
            unit, "lower", source, layer, moves)
        assert "workloads" not in e
        # reported in every cell
        for w in manifest["workloads"]:
            assert name in harness.Cell(w["name"]).metric_names("per_layer")
