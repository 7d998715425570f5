"""admit.seed_launches_per_request (PR 60): the jitted calls of an
admission's ``seed`` stage over the admissions tried, from
gridllm_engine_seed_launches_total and the phase series' count of
``admit``; nothing from a program without the counter, and nothing over a
window that admitted nothing."""
import json
import os

import pytest

import run as harness
from conftest import BENCH, ROOT

NAME = "admit.seed_launches_per_request"


def text(admits: int, launches: int | None = None) -> str:
    lab = '{model="m",phase="admit"}'
    lines = [f"gridllm_engine_phase_seconds_sum{lab} {0.002 * admits}",
             f"gridllm_engine_phase_seconds_count{lab} {admits}"]
    if launches is not None:
        lines.append(f'gridllm_engine_seed_launches_total{{model="m"}} {launches}')
    return "\n".join(lines) + "\n"


def read(before: str, after: str, cell: str = "laguna-xs2.agent_turns"):
    return harness.Cell(cell).reader(NAME).compute(
        {"worker_before": before, "worker_after": after})


def test_launches_over_the_windows_admissions():
    # 230 admissions, nine of ten of them restore a ring beside the seed
    assert read(text(10, 14), text(240, 14 + 230 + 207)) == pytest.approx(1.9)
    # a family with one kind of cache: one launch an admission, cached or not
    assert read(text(4, 4), text(208, 208), "mistral7b.chat") == 1.0
    # the counter first seen inside the window counts from zero
    assert read(text(0), text(50, 75)) == pytest.approx(1.5)


def test_nothing_without_the_counter_or_an_admission():
    assert read(text(10), text(240)) is None          # the parent's program
    assert read("", "") is None
    assert read(text(10, 14), text(10, 14)) is None   # an idle window


def test_the_entry_says_what_the_file_says_and_every_cell_reports_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (e,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = harness.Cell("laguna-xs2.agent_turns").reader(NAME)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        e["name"], e["unit"], e["layer"], e["moves"])
    # beside its sibling admit.seed_ms_per_request: the same layer and the
    # same end-to-end metric, so every cell's line carries both
    (sib,) = [m for m in manifest["per_layer"]
              if m["name"] == "admit.seed_ms_per_request"]
    assert e == {"name": NAME, "unit": "launches", "better": "lower",
                 "source": "program_counter", "layer": sib["layer"],
                 "moves": sib["moves"]}
    for w in manifest["workloads"]:
        assert NAME in harness.Cell(w["name"]).metric_names("per_layer")
