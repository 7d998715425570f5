"""The runner's phase clock (ISSUE 24): the phases partition the runner
thread's wall time on the speculative and the block path, every phase is
observed by a run that admits, drafts and finishes, the engine's queue
wait is measured per request, the verify step's context tokens are
counted, a capture runs with the Python tracer off unless asked, no
TraceAnnotation is constructed while nothing is being captured, and the
drafter's lookups are counted by outcome and ride on the draft span
(ISSUE 37). The second clock (ISSUE 38): a phase's CPU seconds beside its
wall seconds, so that a wait (a sleep, the interpreter lock held by
another thread) reads as blocked time and computing does not; and on a
host whose kernel counts a thread's CPU in ticks the second clock is not
read at all and its series not served. Stages, the unfed clock and the two
stall witnesses (ISSUE 59): a phase divides into stages that sum to at most
the phase and leave the phase's own series as it was, a stage's span
replaces its phase's, the runner's busy time with nothing in flight is
counted and idle time is not, a collection is timed by generation, and an
event loop's lag is read by a timer that stops with its worker."""

import asyncio
import gc

import threading
import time

import numpy as np
import pytest

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.engine.engine import _CHUNK_LAUNCHES, _SPEC_LOOKUPS
from gridllm_tpu.obs import perf
from gridllm_tpu.obs.perf import (
    ADMIT_WAIT_SECONDS,
    GC_PAUSE_SECONDS,
    LOOP_LAG_SECONDS,
    PHASE_CPU_SECONDS_TOTAL,
    PHASE_SECONDS,
    PHASES,
    STAGE_SECONDS,
    UNFED_SECONDS_TOTAL,
    VERIFY_CTX_TOKENS_TOTAL,
    LoopLagTimer,
    PhaseClock,
    ProfilerCapture,
    handle_profile_request,
    install_gc_witness,
    thread_cpu_clock,
)

MODEL = "tiny-llama"
TINY = dict(model=MODEL, max_slots=4, page_size=8, num_pages=64,
            max_pages_per_slot=8, prefill_buckets=(16, 32))
OPTS = {"temperature": 0.0, "num_predict": 12}


def _phase_counts() -> dict[str, int]:
    return {p: PHASE_SECONDS.count(model=MODEL, phase=p) for p in PHASES}


def _phase_sums() -> dict[str, float]:
    return {p: PHASE_SECONDS.sum(model=MODEL, phase=p) for p in PHASES}


# every stage the runner enters (ISSUE 59's table), by phase
STAGES = {"admit": ("tokenize", "match"),
          "dispatch_prefill": ("seed", "chunk", "book"),
          "fetch": ("wait", "copy"), "ingest": ("emit",)}
STAGE_KEYS = [(p, s) for p, ss in STAGES.items() for s in ss]


def _stage_counts() -> dict[tuple[str, str], int]:
    return {k: STAGE_SECONDS.count(model=MODEL, phase=k[0], stage=k[1])
            for k in STAGE_KEYS}


def _stage_sums() -> dict[tuple[str, str], float]:
    return {k: STAGE_SECONDS.sum(model=MODEL, phase=k[0], stage=k[1])
            for k in STAGE_KEYS}


def _phase_cpu() -> dict[str, float]:
    return {p: PHASE_CPU_SECONDS_TOTAL.value(model=MODEL, phase=p)
            for p in PHASES}


def _serve(eng: InferenceEngine, n: int = 3, idle_s: float = 0.3) -> None:
    """Start the runner, leave it idle a moment, serve `n` requests at
    once, stop it."""
    eng.start()
    try:
        time.sleep(idle_s)
        done = []
        for i in range(n):
            eng.submit(GenerationRequest(
                id=f"r{i}", prompt=f"hello there {i}", options=OPTS,
                on_chunk=lambda d, fin, res: done.append(res) if fin else None))
        deadline = time.time() + 120
        while len(done) < n and time.time() < deadline:
            time.sleep(0.01)
        assert len(done) == n and all(r.done_reason == "length" for r in done)
    finally:
        eng.stop()


@pytest.mark.parametrize("spec", [True, False], ids=["speculative", "block"])
def test_phases_partition_the_runners_wall_time(spec):
    """Σ phases = the runner thread's wall time (measured on its own,
    _run entry to exit) within 1 %, and the registry holds what the clock
    holds: there is no `other` phase to hide a stretch in."""
    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=spec,
                                       decode_block=2, pipeline_depth=2))
    before = _phase_sums()
    _serve(eng)
    assert eng.runner_wall_s > 0.3
    total = sum(eng._clock.seconds.values())
    assert total == pytest.approx(eng.runner_wall_s, rel=0.01)
    after = _phase_sums()
    assert sum(after[p] - before[p] for p in PHASES) == pytest.approx(total, rel=1e-6)
    # the idle stretch before the first request is idle_wait's, not a host phase's
    assert eng._clock.seconds["idle_wait"] >= 0.25
    assert eng._clock.seconds["fetch"] > 0 and eng._clock.seconds["ingest"] > 0


def test_every_phase_is_observed_by_a_run_that_admits_drafts_and_finishes():
    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=True))
    before, cpu_before = _phase_counts(), _phase_cpu()
    launches0 = eng._gen
    _serve(eng, n=2)
    after, cpu_after = _phase_counts(), _phase_cpu()
    for p in PHASES:
        assert after[p] > before[p], p
    # admit's count is admissions; dispatch_verify's is launches: the
    # generations less the two mixed launches that admitted
    assert after["admit"] - before["admit"] == 2
    assert (after["dispatch_verify"] - before["dispatch_verify"]
            == eng._gen - launches0 - 2)
    state = eng.batch_state()
    assert set(state["runnerPhaseSeconds"]) == set(PHASES)
    clock = eng._clock
    assert state["runnerPhaseCpuSeconds"].keys() == clock.cpu_seconds.keys()
    if thread_cpu_clock() is None:
        assert not clock.cpu_seconds and cpu_after == cpu_before
        return
    # the second clock: in every phase the runner's CPU time is within
    # its wall time, the counter holds what the clock holds, and the two
    # phases that wait by design are nearly all blocked
    for p in PHASES:
        # (two clocks are read one after the other: 1 ms of room)
        assert 0.0 <= clock.cpu_seconds[p] <= clock.seconds[p] + 1e-3, p
        assert cpu_after[p] - cpu_before[p] == pytest.approx(
            clock.cpu_seconds[p], abs=1e-9), p
    assert clock.cpu_seconds["idle_wait"] < 0.5 * clock.seconds["idle_wait"]
    assert sum(clock.cpu_seconds.values()) > 0.0


def test_admit_wait_is_at_least_an_injected_delay():
    """A request submitted while the runner is not running waits in
    _pending: the wait is observed and rides on the result."""
    eng = InferenceEngine(EngineConfig(**TINY))
    n0, s0 = (ADMIT_WAIT_SECONDS.count(model=MODEL),
              ADMIT_WAIT_SECONDS.sum(model=MODEL))
    box = []
    eng.submit(GenerationRequest(
        id="late", prompt="hello", options=OPTS,
        on_chunk=lambda d, fin, res: box.append(res) if fin else None))
    time.sleep(0.25)
    eng.start()
    try:
        deadline = time.time() + 120
        while not box and time.time() < deadline:
            time.sleep(0.01)
    finally:
        eng.stop()
    assert box and box[0].admit_wait_ns >= 0.25e9
    # the wait is inside the request's prompt-eval time, not beside it
    assert box[0].prompt_eval_duration_ns >= 0
    assert ADMIT_WAIT_SECONDS.count(model=MODEL) == n0 + 1
    assert ADMIT_WAIT_SECONDS.sum(model=MODEL) - s0 >= 0.25


def test_ctx_token_counter_is_the_sum_of_context_lengths_over_dispatches():
    """Two slots, the synchronous driver, speculation off: at every decode
    dispatch the counter grows by Σ over live slots of context length."""
    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=False))
    seen: list[int] = []
    dispatch = eng._dispatch_block

    def counting(k):
        seen.append(sum(len(st.ids) for st in eng._slots.values()))
        dispatch(k)

    eng._dispatch_block = counting
    c0 = VERIFY_CTX_TOKENS_TOTAL.value(model=MODEL)
    n0 = PHASE_SECONDS.count(model=MODEL, phase="dispatch_verify")
    prompts = ["hello", "a longer prompt than that"]
    for i, p in enumerate(prompts):
        eng.submit(GenerationRequest(id=f"c{i}", prompt=p,
                                     options={**OPTS, "num_predict": 5 + i}))
    while eng.step():
        pass
    lens = [len(eng.tokenizer.encode(p, add_bos=True)) for p in prompts]
    # the first launch reads the two prompts, the first stream's first two
    # tokens (its prefill's sample and its decode row in the mixed launch
    # that admitted the second) and the second's first; the next, one
    # token more a stream
    assert seen[0] == sum(lens) + 3 and seen[1] == sum(lens) + 5
    assert len(seen) >= 5
    assert VERIFY_CTX_TOKENS_TOTAL.value(model=MODEL) - c0 == sum(seen)
    assert (PHASE_SECONDS.count(model=MODEL, phase="dispatch_verify") - n0
            == len(seen))


@pytest.fixture
def fake_profiler(monkeypatch, tmp_path):
    """jax.profiler.start_trace / stop_trace replaced by recorders: what
    options a capture passes, without a real trace."""
    import jax

    calls: list = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path, **kw: calls.append((path, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setenv("GRIDLLM_PROFILE_DIR", str(tmp_path))
    return calls


def _wait_idle(prof: ProfilerCapture) -> None:
    deadline = time.time() + 30
    while prof.active is not None and time.time() < deadline:
        time.sleep(0.01)
    assert prof.active is None


@pytest.mark.parametrize("how, want", [
    ("default", 0), ("python=True", 1), ("?python=1", 1), ("?python=0", 0),
])
def test_capture_runs_with_the_python_tracer_off_unless_asked(
        fake_profiler, tmp_path, how, want):
    from gridllm_tpu.obs import default_profiler

    if how.startswith("?"):
        prof = default_profiler()
        _wait_idle(prof)
        status, info = handle_profile_request("0.05", how.split("=")[1])
        assert status == 200
    else:
        prof = ProfilerCapture(base_dir=str(tmp_path))
        info = prof.capture(0.05, **({"python": True} if want else {}))
    assert prof.tracing
    (path, kw), = fake_profiler
    opts = kw["profiler_options"]
    assert opts.python_tracer_level == want
    assert opts.host_tracer_level > 0      # the gridllm.* spans need it
    assert info["python"] is bool(want) and path == info["path"]
    _wait_idle(prof)
    assert not prof.tracing
    assert "stopTraceS" in prof.captures[-1]


class _Tracing:
    tracing = False


@pytest.fixture
def made(monkeypatch):
    """jax.profiler.TraceAnnotation replaced by a recorder: every span
    constructed, in order, with its metadata."""
    import jax

    made: list = []

    class Span:
        def __init__(self, name, **meta):
            self.name, self.meta, self.open = name, dict(meta), None
            made.append(self)

        def __enter__(self):
            # the spans open on this thread when this one opens
            self.under = [s.name for s in made if s.open]
            self.open = True
            self.thread = threading.get_ident()

        def __exit__(self, *exc):
            self.open = False

        def set_metadata(self, **meta):
            self.meta.update(meta)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    return made


def test_no_trace_annotation_is_constructed_with_no_capture_active(made):
    """The phase clock's spans exist only while a capture runs; then every
    phase is a gridllm.<phase> annotation, with the launch's metadata."""
    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=True))
    flag = _Tracing()
    eng._clock = PhaseClock(MODEL, profiler=flag)
    _serve(eng, n=1, idle_s=0.0)
    assert made == []
    flag.tracing = True
    _serve(eng, n=2, idle_s=0.0)
    flag.tracing = False
    names = {s.name for s in made}
    # a fetch is entered in its first stage: all of it is wait or copy
    assert names == ({"gridllm." + p for p in PHASES if p != "fetch"}
                     | {f"gridllm.{p}.{s}" for p, s in STAGE_KEYS})
    assert all(s.open is False for s in made)       # each closed by the next mark
    # flat: a stage's span REPLACES its phase's (closed first, not nested
    # under), so no span ever opens under another
    assert all(s.under == [] for s in made)
    launch = next(s for s in made if s.name == "gridllm.dispatch_verify")
    # what varies by launch; the engine's constants are in batch_state()
    assert set(launch.meta) == {"gen", "slots", "ctx_tokens"}
    assert launch.meta["slots"] >= 1 and launch.meta["ctx_tokens"] > 0
    shape = eng.batch_state()["shape"]
    assert shape["mesh"] == ""                      # unmeshed; "tp:4" under one
    # K and V per head; "latent" / "absorbed" for a latent-attention family
    assert (shape["cacheRow"], shape["attnForm"]) == ("kv", "per_head")
    assert (shape["experts"], shape["expertsHeld"], shape["windowLayers"]) == (
        0, None, 0)
    admit = next(s for s in made if s.name == "gridllm.admit.tokenize")
    assert admit.meta["request"] in ("r0", "r1")
    # back from a stage the phase's span opens again, with the phase's meta
    assert all(s.meta["request"] in ("r0", "r1")
               for s in made if s.name == "gridllm.admit")
    prefill = next(s for s in made if s.name == "gridllm.dispatch_prefill")
    assert prefill.meta["prompt_tokens"] > 0 and "cached_tokens" in prefill.meta
    assert set(prefill.meta) == {"request", "prompt_tokens", "cached_tokens"}
    seed = next(s for s in made if s.name == "gridllm.dispatch_prefill.seed")
    # admit_seed alone, r0's pages found or not: this family restores nothing
    assert seed.meta == {"launches": 1}
    chunk = next(s for s in made if s.name == "gridllm.dispatch_prefill.chunk")
    assert {"width", "start"} <= set(chunk.meta)
    assert any("tokens" in s.meta for s in made if s.name == "gridllm.ingest")
    draft = next(s for s in made if s.name == "gridllm.draft")
    assert set(draft.meta) == {"slots", "hits", "history_tokens"}
    n = len(made)
    _serve(eng, n=1, idle_s=0.0)
    assert len(made) == n


def test_draft_lookups_are_counted_by_outcome_and_ride_on_the_span(made):
    """One verify step over two slots, one whose history ends in a suffix
    it held before and one whose tokens are all distinct: the lookup
    counter moves by one hit and one miss, and the draft span says so."""
    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=True))
    for i in range(2):
        eng.submit(GenerationRequest(id=f"d{i}", prompt=f"hello there {i}",
                                     options=OPTS))
    while not (len(eng._slots) == 2
               and all(st.joined_gen <= eng._gen for st in eng._slots.values())):
        assert eng.step()
    hit, miss = eng._slots.values()
    # the lookup reads the host's history alone: its content is free
    hit.ids[:] = [5 + i % 2 for i in range(len(hit.ids))]
    miss.ids[:] = range(100, 100 + len(miss.ids))
    history = len(hit.ids) + len(miss.ids)

    def lookups():
        return {o: _SPEC_LOOKUPS.value(model=MODEL, outcome=o)
                for o in ("hit", "miss")}

    before = lookups()
    flag = _Tracing()
    eng._clock = PhaseClock(MODEL, profiler=flag)
    flag.tracing = True
    assert eng.step()
    flag.tracing = False
    after = lookups()
    assert {o: after[o] - before[o] for o in after} == {"hit": 1, "miss": 1}
    draft, = (s for s in made if s.name == "gridllm.draft")
    assert draft.meta == {"slots": 2, "hits": 1, "history_tokens": history}
    while eng.step():
        pass


# ---------------------------------------------------------------------------
# the second clock (ISSUE 38): wall beside the thread's CPU time
# ---------------------------------------------------------------------------


def _spin(seconds: float) -> int:
    """Pure Python for `seconds` of wall time: the interpreter lock is
    held but for the switch interval's hand-overs."""
    n, end = 0, time.perf_counter() + seconds
    while time.perf_counter() < end:
        n += 1
    return n


def _clocked(work, phase: str = "ingest") -> tuple[float, float]:
    """(wall, CPU) seconds a fresh clock reads for `work()` in `phase`."""
    clock = PhaseClock("second-clock")
    clock.mark(phase)
    work()
    clock.pause()
    return clock.seconds[phase], clock.cpu_seconds[phase]


def _best(trials: int, work, good) -> tuple[float, float]:
    """The first of `trials` readings that `good` accepts, else the last:
    the suite shares its cores with five other workers, so one reading
    may be preempted; a clock that could not tell waiting from computing
    would fail every one."""
    for _ in range(trials):
        wall, cpu = _clocked(work)
        if good(wall, cpu):
            break
    return wall, cpu


# on a host of tick clocks the second clock is off by design: nothing to hold
needs_thread_clock = pytest.mark.skipif(
    thread_cpu_clock() is None, reason="no thread CPU clock under 1 ms here")


@needs_thread_clock
def test_a_sleep_reads_as_wall_time_and_a_spin_as_cpu_time():
    wall, cpu = _clocked(lambda: time.sleep(0.05))
    assert wall >= 0.05 and cpu < 0.010

    def spin_cpu():
        # 50 ms of this thread's CPU, however long a shared core takes
        end = time.thread_time() + 0.05
        while time.thread_time() < end:
            pass

    wall, cpu = _clocked(spin_cpu)
    assert wall >= 0.05 and 0.035 <= cpu <= wall + 1e-3


@needs_thread_clock
def test_the_interpreter_lock_held_by_another_thread_reads_as_blocked():
    """A phase of pure Python beside a second thread of pure Python waits
    for the interpreter lock about half the time; alone it waits for
    nothing. This is what PR 37 could not see: a phase that computed for
    part of its wall time and waited for the lock the rest."""
    def blocked_share(wall, cpu):
        return (wall - cpu) / wall

    wall, cpu = _best(8, lambda: _spin(0.1),
                      lambda w, c: blocked_share(w, c) < 0.05)
    assert blocked_share(wall, cpu) < 0.05, (wall, cpu)

    stop = threading.Event()

    def other():
        while not stop.is_set():
            sum(range(1000))

    t = threading.Thread(target=other, daemon=True)
    t.start()
    try:
        wall, cpu = _best(3, lambda: _spin(0.2),
                          lambda w, c: blocked_share(w, c) >= 0.20)
    finally:
        stop.set()
        t.join(10)
    assert not t.is_alive()
    assert blocked_share(wall, cpu) >= 0.20, (wall, cpu)


def _ticks(step: float):
    """A thread clock that moves in whole steps of `step` seconds of the
    real one, as a sandboxed kernel's does."""
    return lambda: time.perf_counter() // step * step


@pytest.mark.parametrize("step, found", [(0.0, True), (1e-4, True),
                                         (2e-3, False), (1e-2, False)])
def test_a_thread_clock_is_read_only_where_it_steps_by_under_a_millisecond(
        monkeypatch, step, found):
    """The probe behind every PhaseClock: the kernel's own clock (step 0:
    whatever this host has, which these tests need to be fine) and clocks
    of 0.1 ms steps pass, ticks of 2 and 10 ms (gVisor) do not."""
    if step:
        monkeypatch.setattr(time, "thread_time", _ticks(step))
    thread_cpu_clock.cache_clear()
    try:
        assert (thread_cpu_clock() is not None) is found
    finally:
        thread_cpu_clock.cache_clear()


def test_without_a_fine_thread_clock_nothing_is_read_and_nothing_served(
        monkeypatch):
    """On a host of 10 ms ticks a mark reads the wall clock alone (a tick
    clock's read costs 6 us there and says nothing of a sub-ms stretch),
    ``cpu_seconds`` stays empty and the CPU counter gets no sample, so
    its readers say nothing rather than "all blocked"."""
    reads = []
    monkeypatch.setattr(time, "thread_time",
                        lambda: reads.append(1) or _ticks(1e-2)())
    thread_cpu_clock.cache_clear()
    try:
        clock = PhaseClock("no-thread-clock")
        probed = len(reads)
        assert probed > 0
        for phase in PHASES:
            clock.mark(phase)
        clock.pause()
    finally:
        thread_cpu_clock.cache_clear()
    assert len(reads) == probed
    assert clock.cpu_seconds == {} and all(clock.counts.values())
    assert all(PHASE_SECONDS.count(model="no-thread-clock", phase=p) == 1
               for p in PHASES)
    assert not [labels for labels, _ in PHASE_CPU_SECONDS_TOTAL.items()
                if labels["model"] == "no-thread-clock"]


# ---------------------------------------------------------------------------
# stages, the unfed clock and the stall witnesses (ISSUE 59)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [True, False], ids=["speculative", "block"])
def test_stages_sum_to_at_most_their_phase_and_leave_the_phase_series_alone(spec):
    """Σ stages of a phase <= the phase (the rest is its unstaged time), a
    fetch is all wait and copy, and the phases still partition the
    runner's wall time: a stage never touches its phase's accounting."""
    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=spec,
                                       decode_block=2, pipeline_depth=2))
    before, sums0 = _phase_sums(), _stage_sums()
    _serve(eng)
    clock = eng._clock
    after, sums1 = _phase_sums(), _stage_sums()
    assert sum(clock.seconds.values()) == pytest.approx(eng.runner_wall_s,
                                                        rel=0.01)
    for phase, stages in STAGES.items():
        staged = sum(clock.stage_seconds[phase, s] for s in stages)
        assert 0.0 < staged <= clock.seconds[phase] + 1e-9, phase
        # the registry holds what the clock holds
        assert sum(sums1[phase, s] - sums0[phase, s] for s in stages) == (
            pytest.approx(staged, rel=1e-6))
        assert after[phase] - before[phase] == pytest.approx(
            clock.seconds[phase], rel=1e-6)
    assert (clock.stage_seconds["fetch", "wait"]
            + clock.stage_seconds["fetch", "copy"]) == pytest.approx(
                clock.seconds["fetch"], rel=1e-6)
    assert set(clock.stage_seconds) == set(STAGE_KEYS)     # and nothing else


def test_every_stage_is_observed_with_the_count_its_table_says():
    """admit's stages and dispatch_prefill's seed and book once an
    admission, a chunk stretch a chunk launch, a wait and a copy a fetch
    (verify launches and the mixed launches that admitted), an emit a
    callback."""
    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=True,
                                       prefill_chunk=16))
    calls = []
    before, gen0 = _stage_counts(), eng._gen
    chunks0 = sum(v for _, v in _CHUNK_LAUNCHES.items())
    phase0 = _phase_counts()
    eng.start()
    try:
        done = []
        for i, prompt in enumerate(["hello there", "hello there " * 4]):
            eng.submit(GenerationRequest(
                id=f"s{i}", prompt=prompt, options=OPTS,
                on_chunk=lambda d, fin, res: (calls.append(d),
                                              done.append(res) if fin else None)))
        deadline = time.time() + 120
        while len(done) < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert len(done) == 2
    finally:
        eng.stop()
    got = {k: v - before[k] for k, v in _stage_counts().items()}
    chunks = sum(v for _, v in _CHUNK_LAUNCHES.items()) - chunks0
    assert chunks >= 4                # the longer prompt took three or more
    fetches = _phase_counts()["fetch"] - phase0["fetch"]
    assert fetches == eng._gen - gen0
    assert got == {("admit", "tokenize"): 2, ("admit", "match"): 2,
                   ("dispatch_prefill", "seed"): 2,
                   ("dispatch_prefill", "chunk"): chunks,
                   ("dispatch_prefill", "book"): 2,
                   ("fetch", "wait"): fetches, ("fetch", "copy"): fetches,
                   ("ingest", "emit"): len(calls)}


def test_a_stage_with_no_phase_open_does_nothing():
    """A multi-host follower replays _dispatch_prefill off the runner:
    no phase is open, so nothing is timed and nothing annotated."""
    flag = _Tracing()
    flag.tracing = True
    clock = PhaseClock("follower", profiler=flag)
    assert clock.stage("seed") == 0.0 and clock.stage(None) == 0.0
    clock.fed()
    clock.flush()
    assert clock.stage_seconds == {} and clock._span is None
    assert clock.unfed_seconds == 0.0
    # and a stage of another phase than the one named is not entered
    clock.mark("ctl")
    assert clock.stage("emit", of="ingest") == 0.0
    clock.pause()
    assert clock.stage_seconds == {}


def test_mark_returns_the_whole_phase_whatever_stages_divided_it():
    """_mark_ingest's return feeds usage attribution and the run-ahead
    rule: it reads the closed phase whole, wait and copy together."""
    clock = PhaseClock("whole-phase")
    clock.mark("fetch", stage="wait")
    time.sleep(0.02)
    clock.stage("copy")
    time.sleep(0.02)
    waited = clock.mark("ingest")
    clock.pause()
    assert waited >= 0.04
    assert clock.seconds["fetch"] == pytest.approx(waited)
    assert clock.stage_counts == {("fetch", "wait"): 1, ("fetch", "copy"): 1}
    assert min(clock.stage_seconds.values()) >= 0.02
    assert sum(clock.stage_seconds.values()) == pytest.approx(waited)


def _unfed() -> float:
    return UNFED_SECONDS_TOTAL.value(model=MODEL)


def test_the_unfed_clock_counts_a_series_and_never_idle_wait():
    """In series (the synchronous driver: every launch is fetched before
    the next is dispatched) the chip has nothing in flight from a fetch to
    the next launch: counted. An idle runner is not: nobody asked."""
    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=False))
    u0 = _unfed()
    eng.submit(GenerationRequest(id="u0", prompt="hello there", options=OPTS))
    while eng.step():
        pass
    series = _unfed() - u0
    assert series > 0.0
    clock = eng._clock
    busy = sum(v for p, v in clock.seconds.items() if p != "fetch")
    # the host's phases and the copy, never the fetch's wait
    assert series <= busy + clock.stage_seconds["fetch", "copy"] + 1e-6
    assert series == pytest.approx(eng._clock.unfed_seconds)
    # an idle runner: half a second of idle_wait, not a microsecond unfed
    u1, idle0 = _unfed(), eng._clock.seconds["idle_wait"]
    eng.start()
    time.sleep(0.5)
    eng.stop()
    assert eng._clock.seconds["idle_wait"] - idle0 >= 0.4
    assert _unfed() - u1 < 0.01


def test_the_unfed_clock_stands_still_while_a_launch_is_in_flight():
    """Run-ahead: the block pipeline keeps `pipeline_depth` launches in
    flight, so from its first fetch to its last the clock is never started
    and the counter does not move, whatever the host does in between."""
    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=False,
                                       decode_block=1, pipeline_depth=2))
    clock = eng._clock
    seen = []
    fetch = eng._fetch_oldest

    def watched():
        # behind this fetch another launch is in flight: the pump tops the
        # pipeline up before it fetches
        behind = len(eng._inflight) - 1
        gen = fetch()
        seen.append((behind, clock._unfed_t,
                     clock.unfed_seconds + clock._unfed_acc))
        return gen

    eng._fetch_oldest = watched
    done = []
    eng.submit(GenerationRequest(
        id="a0", prompt="hello there", options={**OPTS, "num_predict": 24},
        on_chunk=lambda d, fin, res: done.append(res) if fin else None))
    eng.start()
    try:
        deadline = time.time() + 120
        while not done and time.time() < deadline:
            time.sleep(0.01)
    finally:
        eng.stop()
    assert len(seen) >= 20 and all(behind == 1 for behind, _, _ in seen)
    assert all(t is None for _, t, _ in seen)
    assert seen[0][2] == seen[-1][2] > 0.0      # the admission's, before it


def test_a_collection_is_timed_by_generation_and_spanned_under_a_capture(
        made, monkeypatch):
    install_gc_witness()
    install_gc_witness()                                # once a process
    assert gc.callbacks.count(perf._on_gc) == 1

    def counts():
        return {g: GC_PAUSE_SECONDS.count(generation=str(g)) for g in range(3)}

    was = gc.isenabled()
    gc.disable()                # no collection but the ones forced here
    try:
        before = counts()
        gc.collect(1)
        assert {g: n - before[g] for g, n in counts().items()} == {
            0: 0, 1: 1, 2: 0}
        assert made == []                               # off a capture: no span
        monkeypatch.setattr(perf._PROFILER, "tracing", True)
        s0 = GC_PAUSE_SECONDS.sum(generation="2")
        gc.collect()
        monkeypatch.setattr(perf._PROFILER, "tracing", False)
        assert counts()[2] == before[2] + 1
        assert GC_PAUSE_SECONDS.sum(generation="2") > s0
    finally:
        if was:
            gc.enable()
    span, = (s for s in made if s.name == "gridllm.gc")
    assert span.meta["generation"] == 2 and span.meta["collected"] >= 0
    assert span.open is False


def test_the_loop_lag_timer_reads_a_blocked_loop_and_stops_with_its_worker():
    async def run():
        timer = LoopLagTimer().start()
        await asyncio.sleep(0.12)                       # two firings on time
        n0, s0 = LOOP_LAG_SECONDS.count(), LOOP_LAG_SECONDS.sum()
        assert n0 >= 1
        await asyncio.sleep(0.001)
        time.sleep(0.1)         # async-ok: the blocked loop is the test
        await asyncio.sleep(0.02)
        late = LOOP_LAG_SECONDS.sum() - s0
        assert LOOP_LAG_SECONDS.count() > n0 and late >= 0.04
        timer.stop()
        n1 = LOOP_LAG_SECONDS.count()
        await asyncio.sleep(0.12)
        assert LOOP_LAG_SECONDS.count() == n1           # nothing re-armed
        timer.stop()                                    # idempotent

    asyncio.run(run())


def test_the_worker_arms_both_witnesses_and_stops_its_timer():
    """WorkerService.start() installs the collector's witness and the
    loop-lag timer; stop() cancels the timer."""
    from gridllm_tpu.bus import create_bus
    from gridllm_tpu.utils.config import WorkerConfig
    from gridllm_tpu.worker.service import WorkerService

    async def run():
        bus = create_bus("")
        worker = WorkerService(bus, {}, WorkerConfig(worker_id="lag-w"))
        await worker.start()
        try:
            assert perf._on_gc in gc.callbacks
            assert worker._loop_lag._handle is not None
            n0 = LOOP_LAG_SECONDS.count()
            await asyncio.sleep(0.12)
            assert LOOP_LAG_SECONDS.count() > n0
        finally:
            await worker.stop()
        assert worker._loop_lag._handle is None

    asyncio.run(run())
