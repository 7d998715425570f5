"""When a stream's frames leave the worker (ISSUE 47): `FramePacer` and
the loop of `WorkerService._run_generation` that it paces.

The pacing tests run the REAL loop on a made-up clock: an event loop whose
`time()` jumps to the next timer instead of sleeping, a scripted engine
that hands `on_chunk` bursts at set instants, and a bus whose `publish`
takes a set round trip of that clock. Nothing here sleeps on the wall.

The invariants, with F the flush window:
(a) two frames of a stream that are not its last are never handed over
    less than F apart;
(b) no delta waits in the worker longer than F plus one publish;
(c) at a steady period of F or more every burst is one frame, the whole
    burst in it; each frame is held F/8 longer than the one before until
    the hold is F, so no gap passes the period by more than F/8; a burst
    behind a stall leaves on arrival, and its gap is the stall less what
    had been held;
(d) at a period under F a frame carries what arrived since the one
    before, and no gap passes the larger of F and the period plus F/8.
(ISSUE 47 asked for (c) as "leaves on arrival": review turned that down,
for it showed the document cells every stall whole: PERF.md, PR 47.)
"""

from __future__ import annotations

import asyncio
import itertools
import json
import types

import pytest

from gridllm_tpu.bus import InMemoryBus
from gridllm_tpu.bus.base import job_stream_channel
from gridllm_tpu.engine import EngineConfig, GenerationResult, InferenceEngine
from gridllm_tpu.obs import default_registry
from gridllm_tpu.utils.config import WorkerConfig
from gridllm_tpu.utils.types import InferenceRequest, JobAssignment
from gridllm_tpu.worker.service import FramePacer, WorkerService

F = 0.020
EPS = 1e-9


# ------------------------------------------------------------ the rule alone

def test_pacer_sends_a_streams_first_burst_at_once():
    p = FramePacer(F)
    p.arrived(123.0)
    assert p.due(123.0) == 123.0


@pytest.mark.parametrize("since, left", [
    (0.0, 0.020), (0.005, 0.015), (0.0199, 0.0001), (0.020, 0.0),
    (0.0225, 0.0), (5.0, 0.0)])
def test_before_a_pace_is_known_a_burst_waits_what_is_left_of_the_window(
        since, left):
    p = FramePacer(F)
    p.arrived(10.0)
    p.mark(10.0)
    t = 10.0 + since        # the rest of a split burst, or a burst's tail
    assert p.pace_s is None
    assert p.due(t) - t == pytest.approx(left, abs=1e-12)


def test_pace_is_the_smoothed_time_between_bursts_not_between_deltas():
    p = FramePacer(F)
    for t in (1.0, 1.0001, 1.0002):          # one launch, three drafts
        p.arrived(t)
    assert p.pace_s is None
    p.arrived(1.0225)
    assert p.pace_s == pytest.approx(0.0225)
    p.arrived(1.0225 + 0.0625)               # a stall: an eighth of it counts
    assert p.pace_s == pytest.approx(0.0225 + 0.040 / 8)


@pytest.mark.parametrize("period_ms", [20, 22.5, 26, 40])
def test_hold_grows_an_eighth_of_the_window_a_frame_up_to_the_window(period_ms):
    period = period_ms / 1e3
    p = FramePacer(F)
    holds = []
    for k in range(14):
        t = 5.0 + k * period
        p.arrived(t)
        due = p.due(t)
        p.mark(due)
        holds.append(due - t)
    assert holds == pytest.approx(
        [min(F, k * F / 8) for k in range(14)], abs=1e-9)


@pytest.mark.parametrize("stall_ms, gap_ms", [
    (30, 25.9375), (45, 27.8125), (60, 40.0), (75, 55.0), (200, 180.0)])
def test_a_burst_behind_a_stall_shows_the_stall_less_what_was_held(
        stall_ms, gap_ms):
    p = FramePacer(F)
    t = due = 0.0
    for k in range(12):                       # a steady 22.5 ms: hold is F
        t = 5.0 + k * 0.0225
        p.arrived(t)
        due = p.due(t)
        p.mark(due)
    assert due - t == pytest.approx(F)
    late = t + stall_ms / 1e3
    p.arrived(late)
    # on arrival, or at the pace the stall itself has lengthened
    assert (p.due(late) - due) * 1e3 == pytest.approx(gap_ms, abs=1e-6)


# ----------------------------------------------- the real loop, a made-up clock

class VirtualLoop(asyncio.SelectorEventLoop):
    """An event loop on a made-up clock: where it would sleep until its
    next timer it moves `time()` there instead."""

    def __init__(self):
        super().__init__()
        self.now = 0.0
        poll = self._selector.select

        def select(timeout=None):
            events = poll(0)
            if not events:
                # nothing scheduled and nothing ready would be a hang
                assert timeout is not None, "the loop would wait for ever"
                self.now += timeout
            return events

        self._selector.select = select

    def time(self) -> float:
        return self.now


class ScriptedEngine:
    """What `_run_generation` needs of an engine; `submit` schedules the
    script's bursts on the loop's clock."""

    embedding_only = False
    tokenizer = None
    config = types.SimpleNamespace(max_slots=1)

    def __init__(self, bursts: list[tuple[float, list[str]]]):
        self.bursts = bursts

    def resolve_seed(self) -> int:
        return 1

    def decode_snapshot(self, request_id: str):
        return None

    def submit(self, gen) -> None:
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        text = "".join(d for _, ds in self.bursts for d in ds)
        n = sum(len(ds) for _, ds in self.bursts)

        def burst(deltas, last):
            # a launch's tokens: one on_chunk each, back to back
            for d in deltas:
                gen.on_chunk(d, False, None)
            if last:
                gen.on_chunk("", True, GenerationResult(
                    id=gen.id, text=text, eval_count=n, done_reason="stop"))

        for i, (t, deltas) in enumerate(self.bursts):
            loop.call_at(t0 + t, burst, deltas, i == len(self.bursts) - 1)


class ClockedBus:
    """Records every stream frame at the instant it is handed over, and
    takes `rtt` of the loop's clock to publish it."""

    def __init__(self, rtt: float):
        self.rtt = rtt
        self.frames: list[tuple[float, dict]] = []

    async def publish(self, channel: str, message: str) -> int:
        if channel.startswith("job:stream:"):
            self.frames.append(
                (asyncio.get_running_loop().time(), json.loads(message)))
        if self.rtt:
            await asyncio.sleep(self.rtt)
        return 1


def request(stream: bool) -> JobAssignment:
    req = InferenceRequest(id="r1", model="m", prompt="p", stream=stream,
                           metadata={"raw": True})
    return JobAssignment(jobId=req.id, workerId="w", request=req)


def paced(bursts, rtt=0.0, stream=True):
    """Run the script through the worker's loop on the made-up clock:
    [(hand-over time, frame)] and the response."""
    loop = VirtualLoop()
    try:
        async def go():
            bus = ClockedBus(rtt)
            svc = WorkerService(bus, {}, WorkerConfig(worker_id="w"))
            svc._snap_every = 0
            t0 = loop.time()
            res = await svc._run_generation(
                ScriptedEngine(bursts), request(stream))
            return [(t - t0, f) for t, f in bus.frames], res
        return loop.run_until_complete(go())
    finally:
        loop.close()


def script(period: float, size: int, n: int = 40):
    """`n` bursts of `size` tokens `period` apart; token k is "<k>"."""
    ids = itertools.count()
    return [(0.05 + i * period, [f"<{next(ids)}>" for _ in range(size)])
            for i in range(n)]


def in_two_wakeups(launches):
    """Each launch's first token, and the rest 1 ms later: what the loop
    sees when it wakes between two of the runner's `on_chunk` calls."""
    return [part for t, ds in launches
            for part in ([(t, ds[:1]), (t + 0.001, ds[1:])] if ds[1:]
                         else [(t, ds)])]


def arrivals(bursts) -> dict[str, float]:
    return {d: t for t, ds in bursts for d in ds}


def tokens_of(frame: dict) -> list[str]:
    return [t + ">" for t in frame["response"].split(">") if t]


@pytest.mark.parametrize("split", [False, True], ids=["whole", "split"])
@pytest.mark.parametrize("rtt_ms", [0, 2, 6])
@pytest.mark.parametrize("size", [1, 2, 3, 4, 5])
@pytest.mark.parametrize("period_ms", [15, 19, 22.5, 26, 40])
def test_pacing_invariants(period_ms, size, rtt_ms, split):
    period, rtt = period_ms / 1e3, rtt_ms / 1e3
    launches = script(period, size)
    bursts = in_two_wakeups(launches) if split else launches
    frames, res = paced(bursts, rtt)
    when = arrivals(bursts)
    body = frames[:-1]            # the last frame is the stream's tail

    # the bytes: every token once, in order, offsets contiguous
    text = "".join(f["response"] for _, f in frames)
    assert text == "".join(d for _, ds in bursts for d in ds) == res.response
    off = 0
    for _, f in frames:
        assert f["offset"] == off
        off += len(f["response"])
    assert frames[-1][1]["eval_count"] == res.eval_count == 40 * size

    # (a) never two frames of the body under F apart
    gaps = [b - a for (a, _), (b, _) in zip(body, body[1:])]
    assert min(gaps) >= F - EPS
    # (b) no token waits longer than F plus one publish
    for t, f in frames:
        assert t - min(when[d] for d in tokens_of(f)) <= F + rtt + EPS
    # (c), (d) no gap passes the period by more than F / 8, nor F where
    # that is more; a frame carries what arrived since the one before
    assert max(gaps) <= max(F, period + F / 8) + EPS
    for (t_prev, _), (t, f) in zip(frames, frames[1:]):
        assert all(t_prev - EPS <= when[d] <= t + EPS for d in tokens_of(f))
    if period >= F:
        # (c) a frame a launch, the whole launch in it: but for the first,
        # whose first token leaves as it comes whatever follows it
        first = 2 if split and size > 1 else 1
        assert len(frames) == len(launches) + first - 1
        for (t, f), (t_in, deltas) in zip(frames[first:], launches[1:]):
            assert tokens_of(f) == deltas
        if not split:
            # each frame held F / 8 longer than the one before, up to F
            for k, ((t, _), (t_in, _)) in enumerate(zip(body, launches)):
                assert t - t_in == pytest.approx(min(F, k * F / 8), abs=EPS)


def test_the_old_failure_replayed():
    """Period 22.5 ms, a 2 ms publish: the loop as it was buffered a
    launch whenever `period - publish < F`, then sent it a whole F later
    (gaps of period + F, 42.5 ms, and every frame after it F late). No
    gap now passes the period by more than F / 8, and once the hold is
    full every gap is the period."""
    bursts = script(0.0225, 2, n=200)
    frames, _ = paced(bursts, rtt=0.002)
    gaps = [b - a for (a, _), (b, _) in zip(frames, frames[1:])]
    assert len(frames) == 200
    assert max(gaps) <= 0.0225 + F / 8 + EPS
    assert gaps[8:-1] == pytest.approx([0.0225] * 190, abs=EPS)


def test_a_burst_that_comes_in_two_parts_costs_one_window_once():
    """The second part of a stream's first burst arrives after the first
    has left: it goes at the window's end. Later bursts are held, so
    their parts leave together."""
    bursts = [(0.100, ["<0>"]), (0.101, ["<1>"]), (0.1225, ["<2>"]),
              (0.1235, ["<3>"]), (0.145, ["<4>"]), (0.200, ["<5>"])]
    frames, _ = paced(bursts)
    assert [round(t, 4) for t, _ in frames] == [0.1, 0.12, 0.1425, 0.165, 0.2]
    assert [tokens_of(f) for _, f in frames] == [
        ["<0>"], ["<1>"], ["<2>", "<3>"], ["<4>"], ["<5>"]]


@pytest.mark.parametrize("stall_ms", [45, 60, 75, 82])
def test_a_stall_is_shown_less_what_was_held_and_the_hold_comes_back(stall_ms):
    """Twelve launches 22.5 ms apart, a stall, twelve more: the frame
    behind the stall leaves as it comes (the gap is the stall less the
    20 ms held), and the frames after it are held a little longer each
    until the hold is the window again."""
    stall = stall_ms / 1e3
    before = [(0.05 + k * 0.0225, [f"<{k}>"]) for k in range(12)]
    t_stall = before[-1][0] + stall
    after = [(t_stall + k * 0.0225, [f"<{12 + k}>"]) for k in range(13)]
    frames, _ = paced(before + after)
    times = [t for t, _ in frames]
    assert len(frames) == 25
    assert times[11] - before[11][0] == pytest.approx(F)
    assert times[12] - times[11] == pytest.approx(
        max(stall - F, 0.0225 + (stall - 0.0225) / 8 + F / 8), abs=EPS)
    holds = [t - t_in for t, (t_in, _) in zip(times[12:24], after)]
    assert all(b >= a - EPS for a, b in zip(holds, holds[1:]))
    assert holds[0] == pytest.approx(max(0.0, times[12] - t_stall), abs=EPS)
    assert holds[-1] == pytest.approx(F)
    assert max(b - a for a, b in zip(times[12:24], times[13:24])) <= (
        0.0225 + stall / 8 + F / 8)


def test_a_stream_that_is_not_streamed_sends_no_frame():
    frames, res = paced(script(0.0225, 2, n=5), stream=False)
    assert frames == [] and res.response == "".join(f"<{i}>" for i in range(10))


def series(name: str, **labels) -> float:
    m = default_registry().get(name)
    return m.value(**labels) if labels else m.count()


def test_the_two_series_count_frames_by_reason_and_their_hold():
    """`immediate`, `deadline` and `final`, one hold observation a frame,
    and both series in the text `/metrics` serves."""
    before = {r: series("gridllm_worker_stream_frames_total", reason=r)
              for r in ("immediate", "deadline", "final")}
    n0 = series("gridllm_worker_stream_hold_seconds")
    s0 = default_registry().get("gridllm_worker_stream_hold_seconds").sum()
    # 15 ms apart: the first leaves at once, the rest at the window's end
    frames, _ = paced(script(0.015, 1, n=9))
    after = {r: series("gridllm_worker_stream_frames_total", reason=r)
             for r in before}
    sent = {r: after[r] - before[r] for r in before}
    assert sent["final"] == 1 and sent["immediate"] >= 1
    assert sent["deadline"] >= 4
    assert sum(sent.values()) == len(frames)
    assert series("gridllm_worker_stream_hold_seconds") - n0 == len(frames)
    held = default_registry().get("gridllm_worker_stream_hold_seconds").sum() - s0
    assert 0 < held / len(frames) < F
    text = default_registry().render()
    for r in ("immediate", "deadline", "final"):
        assert f'gridllm_worker_stream_frames_total{{reason="{r}"}}' in text
    assert 'gridllm_worker_stream_hold_seconds_bucket{le="0.00025"}' in text
    assert 'gridllm_worker_stream_hold_seconds_bucket{le="0.05"}' in text


# ------------------------------------- a real engine behind the memory bus

MODEL = "tiny-llama"
PROMPT = "the quick brown fox jumps over the lazy dog " * 2
N_PREDICT = 40


def make_engine() -> InferenceEngine:
    return InferenceEngine(EngineConfig(
        model=MODEL, max_slots=2, page_size=8, num_pages=96,
        max_pages_per_slot=16, prefill_buckets=(16, 64, 128), seed=42,
        prefill_chunk=16))


class BlippingBus:
    """The memory bus, with chosen publishes of stream frames failing."""

    def __init__(self, inner, fail_frames: set[int]):
        self._inner, self.fail, self.seen = inner, fail_frames, 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    async def publish(self, channel: str, message: str):
        if channel.startswith("job:stream:"):
            self.seen += 1
            if self.seen in self.fail:
                raise ConnectionError("bus blip")
        return await self._inner.publish(channel, message)


_ids = itertools.count()


async def serve(engine, svc_bus, mem, stream: bool, **metadata):
    """One request through a WorkerService's loop: the frames a subscriber
    of the memory bus received, the response, the engine's own result."""
    svc = WorkerService(svc_bus, {MODEL: engine}, WorkerConfig(worker_id="w"),
                        stream_flush_ms=5)
    svc._snap_every = 0
    req = InferenceRequest(
        id=f"pace-{next(_ids)}", model=MODEL, prompt=PROMPT, stream=stream,
        options={"num_predict": N_PREDICT, "temperature": 0},
        metadata=metadata)
    frames: list[dict] = []
    results: list[GenerationResult] = []

    async def on_frame(_channel, message):
        frames.append(json.loads(message))

    class Tap:                      # the engine's result, as it hands it on
        def __getattr__(self, name):
            return getattr(engine, name)

        def submit(self, gen):
            on_chunk = gen.on_chunk

            def tapped(delta, done, res):
                if done:
                    results.append(res)
                on_chunk(delta, done, res)

            gen.on_chunk = tapped
            engine.submit(gen)

    sub = await mem.subscribe(job_stream_channel(req.id), on_frame)
    try:
        res = await svc._run_generation(
            Tap(), JobAssignment(jobId=req.id, workerId="w", request=req))
        await asyncio.sleep(0.05)      # the memory bus delivers from tasks
    finally:
        await sub.unsubscribe()
    return frames, res, results[0]


async def test_streamed_frames_are_the_unstreamed_text_through_a_blip_and_a_resume():
    engine = make_engine()
    engine.start()
    mem = InMemoryBus()
    await mem.connect()
    try:
        none, ref, ref_gen = await serve(engine, mem, mem, stream=False)
        text = ref.response
        n = len(ref_gen.token_ids)
        assert none == [] and n > 16 and len(text) > 8

        def check(frames, res, start=0):
            off = start
            for f in frames:
                assert f["offset"] == off
                off += len(f["response"])
            assert "".join(f["response"] for f in frames) == text[start:]
            assert res.response == text
            assert res.eval_count == ref.eval_count
            evals = [f["eval_count"] for f in frames]
            assert evals == sorted(evals) and evals[-1] <= n

        frames, res, _ = await serve(engine, mem, mem, stream=True)
        check(frames, res)
        assert len(frames) > 4

        # a blip: the third and fourth frames' publishes fail; the worker
        # keeps them (`_frame_buf`) and the next frame carries them
        blip = BlippingBus(mem, {3, 4})
        blipped, res, _ = await serve(engine, blip, mem, stream=True)
        assert blip.seen > 4
        check(blipped, res)

        # a resumed attempt: what the client had after the second frame
        # of the first run rides in, and frames start at that offset
        had = frames[1]
        sent = had["offset"] + len(had["response"])
        resumed, res, _ = await serve(
            engine, mem, mem, stream=True,
            resume={"tokens": ref_gen.token_ids[:had["eval_count"]],
                    "sentChars": sent, "seed": 1})
        assert 0 < sent < len(text)
        check(resumed, res, start=sent)
    finally:
        await mem.disconnect()
        engine.stop()
