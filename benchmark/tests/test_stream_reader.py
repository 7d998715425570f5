"""ISSUE 47's reader `stream.hold_mean_ms` over two hand-written
``/metrics`` texts: the mean to the digit, nothing (and no error) where
the window sent no frame or the program has no such series (the parent's
side of the comparison), and the entry beside its file."""
import json
import os

import pytest

import run as harness
from conftest import BENCH, ROOT

NAME = "stream.hold_mean_ms"
SERIES = "gridllm_worker_stream_hold_seconds"


def text(count: float, total: float, under_1ms: float) -> str:
    return "\n".join([
        f'{SERIES}_bucket{{le="0.001"}} {under_1ms}',
        f'{SERIES}_bucket{{le="0.05"}} {count}',
        f'{SERIES}_bucket{{le="+Inf"}} {count}',
        f"{SERIES}_sum {total}", f"{SERIES}_count {count}",
        'gridllm_worker_stream_frames_total{reason="immediate"} 1']) + "\n"


def read(run: dict):
    return harness.Cell("mistral7b.chat").reader(NAME).compute(run)


def test_the_mean_of_the_window_to_the_digit():
    """Warm-up left 100 frames that held 1.5 s; the window added 4,000
    frames and 6.0 s: 1.5 ms a frame, whatever came before."""
    run = {"worker_before": text(100, 1.5, 10),
           "worker_after": text(4100, 7.5, 3000)}
    assert read(run) == pytest.approx(1.5)


@pytest.mark.parametrize("before, after", [
    (text(100, 1.5, 10), text(100, 1.5, 10)),     # no frame in the window
    ("", ""),                                      # no such series: the parent
    ("other_series 1\n", "other_series 5\n")])
def test_nothing_where_there_is_nothing_to_read(before, after):
    assert read({"worker_before": before, "worker_after": after}) is None


def test_the_entry_and_the_file_agree_and_every_cell_reports_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(e for e in manifest["per_layer"] if e["name"] == NAME)
    assert entry == {"name": NAME, "unit": "ms", "better": "lower",
                     "source": "program_counter",
                     "layer": "HTTP API / worker", "moves": "itl_p95_ms"}
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", NAME + ".py"))
    mod = harness.Cell("mistral7b.chat").reader(NAME)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        NAME, entry["unit"], entry["layer"], entry["moves"])
    for w in manifest["workloads"]:
        assert NAME in harness.Cell(w["name"]).metric_names("per_layer")
