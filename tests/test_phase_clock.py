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
read at all and its series not served."""

import threading
import time

import pytest

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.engine.engine import _SPEC_LOOKUPS
from gridllm_tpu.obs.perf import (
    ADMIT_WAIT_SECONDS,
    PHASE_CPU_SECONDS_TOTAL,
    PHASE_SECONDS,
    PHASES,
    VERIFY_CTX_TOKENS_TOTAL,
    PhaseClock,
    ProfilerCapture,
    handle_profile_request,
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
            self.open = True

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
    assert names == {"gridllm." + p for p in PHASES}
    assert all(s.open is False for s in made)       # each closed by the next mark
    launch = next(s for s in made if s.name == "gridllm.dispatch_verify")
    assert set(launch.meta) == {"gen", "slots", "ctx_tokens", "mesh", "experts",
                                "window_layers", "cache_row", "attn_form"}
    assert launch.meta["mesh"] == ""                # unmeshed; "tp:4" under one
    # K and V per head; "latent" / "absorbed" for a latent-attention family
    assert (launch.meta["cache_row"], launch.meta["attn_form"]) == ("kv", "per_head")
    assert launch.meta["slots"] >= 1 and launch.meta["ctx_tokens"] > 0
    admit = next(s for s in made if s.name == "gridllm.admit")
    assert admit.meta["request"] in ("r0", "r1")
    prefill = next(s for s in made if s.name == "gridllm.dispatch_prefill")
    assert prefill.meta["prompt_tokens"] > 0 and "cached_tokens" in prefill.meta
    assert prefill.meta["mesh"] == ""
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
