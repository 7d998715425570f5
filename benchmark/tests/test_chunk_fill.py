"""``engine.chunk_fill_pct`` (PR 32) on recorded ``/metrics`` text: the
window's real over padded chunk tokens; nothing, and no error, from a
program without the counter or a window with no chunk launch; and the
entry that puts it in every cell."""
import pytest

import run as harness

CELLS = ("mistral7b.chat", "mistral7b.shared_doc", "nemo12b-tp4.chat")
# the worker's scrape as the program renders it: both counters, and a
# second model's series that the sum takes in too
TEXT = ('gridllm_engine_chunk_launches_total{{model="m",width="1024"}} {wide}\n'
        'gridllm_engine_chunk_launches_total{{model="m",width="256"}} {narrow}\n'
        'gridllm_engine_chunk_tokens_total{{model="m",kind="padded"}} {padded}\n'
        'gridllm_engine_chunk_tokens_total{{model="m",kind="real"}} {real}\n'
        'gridllm_engine_tokens_total{{model="m",kind="prefill"}} 99999\n')


def reader():
    return harness.Cell("mistral7b.shared_doc").reader("engine.chunk_fill_pct")


def test_fill_is_real_over_padded_in_the_window():
    # prewarm's three launches (1024 + 1 + 1 real in 1024 + 256 + 256) are
    # before the window and cancel
    before = TEXT.format(wide=1, narrow=2, padded=1536, real=1026)
    # a 2,624-token document cold (1024 + 1024 + 576 in three wide chunks)
    # and two re-asks of 128 fresh tokens at the narrow width
    after = TEXT.format(wide=4, narrow=4, padded=1536 + 3072 + 512,
                        real=1026 + 2624 + 256)
    got = reader().compute({"worker_before": before, "worker_after": after})
    assert got == pytest.approx(100.0 * 2880 / 3584)
    # the same window with every last chunk at 1,024, as the parent pads
    parent_like = TEXT.format(wide=8, narrow=0, padded=1536 + 3072 + 2048,
                              real=1026 + 2624 + 256)
    wide = reader().compute({"worker_before": before,
                             "worker_after": parent_like})
    assert wide == pytest.approx(100.0 * 2880 / 5120) and wide < got


def test_an_absent_counter_gives_nothing():
    old = 'gridllm_engine_tokens_total{model="m",kind="prefill"} 5\n'
    assert reader().compute({"worker_before": old, "worker_after": old}) is None
    assert reader().compute({"worker_before": "", "worker_after": ""}) is None


def test_a_window_with_no_chunk_launch_gives_nothing():
    same = TEXT.format(wide=1, narrow=2, padded=1536, real=1026)
    assert reader().compute({"worker_before": same, "worker_after": same}) is None


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_it(cell):
    c = harness.Cell(cell)
    entry, = [m for m in c.manifest["per_layer"]
              if m["name"] == "engine.chunk_fill_pct"]
    assert entry == {"name": "engine.chunk_fill_pct", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "engine admission", "moves": "ttft_p50_ms"}
    assert "engine.chunk_fill_pct" in c.metric_names("per_layer")
    # judged end to end where the median first token is (PERF.md section 2:
    # not in mistral7b.shared_doc), read in every cell
    assert ("ttft_p50_ms" in c.metric_names("end_to_end")) == (
        cell != "mistral7b.shared_doc")
    mod = c.reader("engine.chunk_fill_pct")
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry["name"], entry["unit"], entry["layer"], entry["moves"])
