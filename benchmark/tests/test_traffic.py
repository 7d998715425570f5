"""The generator is a pure function of (mix, rate, seconds, seed)."""
import json
import os

import pytest

import trafficgen
from conftest import BENCH


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("kind", ["chat", "shared_doc"])
def test_same_seed_same_schedule(kind):
    a = trafficgen.generate(mix(kind), 5.0, 40, 3_000_000_007)
    b = trafficgen.generate(mix(kind), 5.0, 40, 3_000_000_007)
    assert a == b
    c = trafficgen.generate(mix(kind), 5.0, 40, 1)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("kind", ["chat", "shared_doc"])
def test_every_seed_carries_the_same_work(kind):
    a = trafficgen.generate(mix(kind), 5.0, 40, 1)
    b = trafficgen.generate(mix(kind), 5.0, 40, 2**31 + 5)
    assert len(a) == len(b) == 200 if kind == "chat" else len(a) == len(b)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.num_predict for r in a) == sorted(r.num_predict for r in b)
    assert [r.due_s for r in a] != [r.due_s for r in b]
    # ... and the same neighbours: b's cycle is a's, entered elsewhere
    first = lambda reqs: [len(r.prompt) for r in sorted(reqs, key=lambda r: (r.group, r.due_s))]
    la, lb = first(a), first(b)
    assert any(lb == la[k:] + la[:k] for k in range(len(la)))


def test_chat_lengths_inside_the_clips_and_due_inside_the_window():
    reqs = trafficgen.generate(mix("chat"), 6.0, 40, 7)
    assert len(reqs) == 240
    assert all(32 <= len(r.prompt) <= 2048 for r in reqs)
    assert all(16 <= r.num_predict <= 256 for r in reqs)
    assert all(0 <= r.due_s < 40 for r in reqs)
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    assert len({r.prompt[:128] for r in reqs}) == len(reqs)   # no shared first page
    assert all(r.prompt.isascii() for r in reqs)
    med = sorted(len(r.prompt) for r in reqs)[len(reqs) // 2]
    assert 230 <= med <= 290


def test_shared_doc_reasks_share_exactly_the_document_bytes():
    reqs = trafficgen.generate(mix("shared_doc"), 4.5, 40, 11)
    groups = {}
    for r in reqs:
        groups.setdefault(r.group, []).append(r)
    assert len(groups) == 60 and all(len(g) == 3 for g in groups.values())
    for g in groups.values():
        g.sort(key=lambda r: r.due_s)
        n = g[0].shared_bytes
        assert 1536 <= n <= 4096
        assert {r.shared_bytes for r in g} == {n}
        assert len({r.prompt[:n] for r in g}) == 1           # the document
        assert len({r.prompt[n:] for r in g}) == 3           # three questions
        assert all(len(r.prompt) == n + 64 and r.num_predict == 48 for r in g)
        assert g[1].due_s - g[0].due_s == pytest.approx(4.0)
        assert g[2].due_s - g[0].due_s == pytest.approx(8.0)
    assert all(0 <= r.due_s < 40 for r in reqs)
    docs = {g[0].prompt[:g[0].shared_bytes][:128] for g in groups.values()}
    assert len(docs) == 60


def test_bursts_move_arrivals_not_counts():
    m = mix("chat")
    flat = trafficgen.generate(m, 5.0, 40, 3)
    m["bursts"] = [{"seconds": 5, "factor": 2.0}, {"seconds": 5, "factor": 0.4}]
    bursty = trafficgen.generate(m, 5.0, 40, 3)
    assert len(bursty) == len(flat)
    on = sum(1 for r in bursty if (r.due_s % 10) < 5)
    assert on > 0.75 * len(bursty)          # 2.0 / (2.0 + 0.4) of the arrivals
    assert all(0 <= r.due_s <= 40 for r in bursty)


def test_two_streams_share_the_rate():
    m = {"streams": [dict(mix("chat")["streams"][0], share=0.5),
                     dict(mix("shared_doc")["streams"][0], share=0.5)]}
    reqs = trafficgen.generate(m, 6.0, 40, 5)
    assert sum(r.stream == "chat" for r in reqs) == 120
    assert sum(r.stream == "doc" for r in reqs) == 120
