"""spec.runahead_pct (PR 54): the share of the chain path's verify launches
that the runner dispatched ahead of a fetch, from
gridllm_spec_launches_total; nothing from a program without the counter."""
import json
import os

import pytest

import run as harness
from conftest import BENCH, ROOT

NAME = "spec.runahead_pct"


def text(serial=None, ahead=None) -> str:
    return "".join(
        f'gridllm_spec_launches_total{{model="m",mode="{mode}"}} {v}\n'
        for mode, v in (("serial", serial), ("ahead", ahead)) if v is not None)


def read(before: str, after: str):
    return harness.Cell("laguna-xs2.agent_turns").reader(NAME).compute(
        {"worker_before": before, "worker_after": after})


def test_the_share_of_the_windows_launches():
    assert read(text(40, 100), text(140, 1000)) == pytest.approx(90.0)
    # the counter is there and no launch of the window ran ahead: 0, said
    assert read(text(40), text(540)) == 0.0
    assert read(text(40, 10), text(40, 110)) == pytest.approx(100.0)


def test_nothing_without_the_counter_or_a_launch():
    assert read("", "") is None                       # the parent's program
    assert read(text(40, 100), text(40, 100)) is None  # an idle window


def test_the_entry_says_what_the_file_says_and_every_cell_reports_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    e = manifest["per_layer"][-1]
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = harness.Cell("laguna-xs2.agent_turns").reader(NAME)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        e["name"], e["unit"], e["layer"], e["moves"])
    assert e == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter",
                 "layer": "engine runner (host loop)", "moves": "ttft_p50_ms"}
    for w in manifest["workloads"]:
        assert NAME in harness.Cell(w["name"]).metric_names("per_layer")
