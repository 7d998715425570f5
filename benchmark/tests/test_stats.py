"""Percentile and gap arithmetic on hand-made samples."""
import pytest

import stats
from loadgen import Outcome


def outcome(i, due, frames, done=True, error=None, eval_count=None):
    o = Outcome(i, due, sent=due + 0.001, frames=frames, done=done, error=error)
    o.eval_count = eval_count if eval_count is not None else sum(c for _, c in frames)
    return o


@pytest.mark.parametrize("q,want", [(0.5, 5), (0.95, 10), (0.9, 9), (0.0, 1), (1.0, 10)])
def test_percentile_nearest_rank(q, want):
    assert stats.percentile(list(range(10, 0, -1)), q) == want


@pytest.mark.parametrize("n,beyond", [(200, 10), (240, 12), (20, 1), (19, 0)])
def test_samples_beyond_the_95th(n, beyond):
    assert stats.samples_beyond(n, 0.95) == beyond


def test_a_failed_request_sits_at_the_largest_finite_value():
    outs = [outcome(0, 10.0, [(10.1, 1), (10.2, 1)]),
            outcome(1, 11.0, [(11.3, 2)], done=False),           # unfinished
            outcome(2, 12.0, [], error="HTTP 503"),               # refused
            outcome(3, 13.0, [(13.05, 1)])]
    t = stats.ttfts_ms(outs, 50_000.0)
    assert t == pytest.approx([100.0, 50_000.0, 50_000.0, 50.0])
    assert stats.percentile(t, 0.95) == 50_000.0
    assert [stats.failed(o) for o in outs] == [False, True, True, False]


def test_gaps_are_pooled_over_streams():
    outs = [outcome(0, 0.0, [(1.0, 1), (1.02, 1), (1.05, 1)]),
            outcome(1, 0.0, [(2.0, 1), (2.1, 1)]),
            outcome(2, 0.0, [(3.0, 1)])]
    assert sorted(stats.gaps_ms(outs, 9e9)) == pytest.approx([20.0, 30.0, 100.0])
    outs.append(outcome(3, 0.0, [(4.0, 1), (4.01, 1)], done=False))
    assert max(stats.gaps_ms(outs, 50_000.0)) == 50_000.0


def test_tokens_inside_the_window_scaled_to_eval_count():
    outs = [outcome(0, 0.0, [(1.0, 2), (2.0, 2)]),                 # 4 chars = 4 tokens
            outcome(1, 0.0, [(1.5, 1), (9.0, 1)], eval_count=4)]   # 2 chars = 4 tokens
    assert stats.tokens_by(outs, 5.0) == pytest.approx(4 + 2)
    assert stats.tokens_by(outs, 10.0) == pytest.approx(8)


def test_end_to_end_never_carries_a_non_number():
    outs = [outcome(0, 100.0, [], error="boom")]
    e = stats.end_to_end(outs, 100.0, 40.0, 10.0)
    assert e["ttft_p50_ms"] == e["ttft_p95_ms"] == e["itl_p95_ms"] == 50_000.0
    assert e["out_tok_s"] == 0.0


def test_well_formed():
    ok = outcome(0, 0.0, [(1.0, 3)], eval_count=3)
    assert stats.malformed(ok, 3) is None
    short = outcome(1, 0.0, [(1.0, 2)], eval_count=2)
    short.done_reason = "length"
    assert "eval_count=2" in stats.malformed(short, 3)
    short.done_reason = "stop"                                      # an EOS
    assert stats.malformed(short, 3) is None


@pytest.mark.parametrize("cell", ["mistral7b.shared_doc", "dsv2lite.shared_doc",
                                  "kimilinear.agent_turns"])
def test_first_token_readers_of_the_cells_that_do_not_judge_the_median(cell):
    """`gen.ttft_p50_ms` is `ttft_p50_ms`'s own arithmetic on the traced
    run's outcomes, and `gen.ttft_mean_ms` the mean of the same samples: a
    failed request enters both at window + drain; no outcomes, no number."""
    import run as harness

    c = harness.Cell(cell)
    assert "ttft_p50_ms" not in c.metric_names("end_to_end")
    outs = [outcome(0, 10.0, [(10.06, 1)]), outcome(1, 11.0, [(11.08, 1)]),
            outcome(2, 12.0, [(12.25, 1)]), outcome(3, 13.0, [], error="HTTP 503")]
    w = {"outcomes": outs, "seconds": 40.0, "drain_s": 10.0}
    e2e = stats.end_to_end(outs, 10.0, 40.0, 10.0)
    p50, mean = c.reader("gen.ttft_p50_ms"), c.reader("gen.ttft_mean_ms")
    assert p50.compute(w) == e2e["ttft_p50_ms"] == pytest.approx(80.0)
    assert mean.compute(w) == pytest.approx((60.0 + 80.0 + 250.0 + 50_000.0) / 4)
    assert p50.compute({**w, "outcomes": []}) is None
    assert mean.compute({**w, "outcomes": []}) is None
    for mod in (p50, mean):
        entry, = [m for m in c.manifest["per_layer"] if m["name"] == mod.NAME]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.CELLS) == (
            entry["unit"], entry["layer"], entry["moves"], entry["workloads"])
        assert mod.MOVES in c.metric_names("end_to_end") and cell in mod.CELLS
