"""The three host-loop readers of ISSUE 38 (the wall time of
``dispatch_verify`` and of ``ctl`` a launch, the drafter's hit share), each
over two hand-written ``/metrics`` texts: values to the digit, nothing
(and no error) where the launches or the lookups are missing, and every
new ``per_layer`` entry has its file."""
import json
import os

import pytest

import run as harness
from conftest import BENCH, ROOT

NEW = {"runner.dispatch_verify_ms_per_step": ("ms", "lower", "program_span"),
       "runner.ctl_ms_per_step": ("ms", "lower", "program_span"),
       "spec.draft_hit_pct": ("%", "higher", "program_counter")}

# phase: (wall s, stretches) when the window opens and after it
BEFORE = {"idle_wait": (10.0, 20), "ctl": (0.10, 100), "admit": (0.20, 10),
          "dispatch_prefill": (0.50, 10), "draft": (0.05, 100),
          "dispatch_verify": (0.40, 100), "fetch": (2.00, 100),
          "ingest": (0.30, 100)}
AFTER = {"idle_wait": (12.0, 24), "ctl": (0.60, 1100), "admit": (0.70, 60),
         "dispatch_prefill": (2.50, 60), "draft": (0.25, 1100),
         "dispatch_verify": (2.40, 1100), "fetch": (24.00, 1100),
         "ingest": (1.80, 1100)}


def text(table: dict, hit: float | None = None,
         miss: float | None = None) -> str:
    lines = []
    for phase, (wall, n) in table.items():
        lab = f'{{model="m",phase="{phase}"}}'
        lines += [f"gridllm_engine_phase_seconds_sum{lab} {wall}",
                  f"gridllm_engine_phase_seconds_count{lab} {n}"]
    for outcome, v in (("hit", hit), ("miss", miss)):
        if v is not None:
            lines.append('gridllm_spec_draft_lookups_total'
                         f'{{model="m",outcome="{outcome}"}} {v}')
    return "\n".join(lines) + "\n"


def a_run() -> dict:
    return {"worker_before": text(BEFORE, hit=100, miss=300),
            "worker_after": text(AFTER, hit=2500, miss=3900)}


def read(name: str, run: dict):
    return harness.Cell("mistral7b.chat").reader(name).compute(run)


def test_readers_to_the_digit():
    """1,000 launches, 2,400 hits and 3,600 misses in the window."""
    run = a_run()
    assert read("runner.dispatch_verify_ms_per_step", run) == pytest.approx(2.0)
    assert read("runner.ctl_ms_per_step", run) == pytest.approx(0.5)
    assert read("spec.draft_hit_pct", run) == pytest.approx(40.0)
    # the wall readers are parts of runner.host_ms_per_step: with draft,
    # ingest and admission they add up to it
    parts = sum(read(n, run) for n in (
        "runner.dispatch_verify_ms_per_step", "runner.ctl_ms_per_step",
        "runner.ingest_ms_per_step", "runner.draft_ms_per_step"))
    admission = read("runner.admit_ms_per_request", run) * 50 / 1000
    assert parts + admission == pytest.approx(
        read("runner.host_ms_per_step", run))


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_without_a_launch_or_a_lookup(name):
    """A window in which nothing was launched or looked up (an idle
    worker; speculation off), and a program with none of the series."""
    idle = {"worker_before": text(BEFORE, hit=100, miss=300),
            "worker_after": text(BEFORE, hit=100, miss=300)}
    assert read(name, idle) is None
    assert read(name, {"worker_before": "", "worker_after": ""}) is None
    no_lookups = {"worker_before": text(BEFORE), "worker_after": text(AFTER)}
    assert read("spec.draft_hit_pct", no_lookups) is None


def test_every_new_entry_has_its_file_and_the_file_says_what_the_entry_says():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"] if m["name"] in NEW}
    assert set(entries) == set(NEW)
    cell = harness.Cell("mistral7b.chat")
    for name, (unit, better, source) in NEW.items():
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
        mod, e = cell.reader(name), entries[name]
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            name, e["unit"], e["layer"], e["moves"])
        assert (e["unit"], e["better"], e["source"]) == (unit, better, source)
        assert e["layer"] == "engine runner (host loop)"
        assert e["moves"] == "itl_p95_ms" and "workloads" not in e
        # reported in every cell
        for w in manifest["workloads"]:
            assert name in harness.Cell(w["name"]).metric_names("per_layer")
