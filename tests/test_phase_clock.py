"""The runner's phase clock (ISSUE 24): the phases partition the runner
thread's wall time on the speculative and the block path, every phase is
observed by a run that admits, drafts and finishes, the engine's queue
wait is measured per request, the verify step's context tokens are
counted, a capture runs with the Python tracer off unless asked, no
TraceAnnotation is constructed while nothing is being captured, and the
drafter's lookups are counted by outcome and ride on the draft span
(ISSUE 37)."""

import time

import pytest

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.engine.engine import _SPEC_LOOKUPS
from gridllm_tpu.obs.perf import (
    ADMIT_WAIT_SECONDS,
    PHASE_SECONDS,
    PHASES,
    VERIFY_CTX_TOKENS_TOTAL,
    PhaseClock,
    ProfilerCapture,
    handle_profile_request,
)

MODEL = "tiny-llama"
TINY = dict(model=MODEL, max_slots=4, page_size=8, num_pages=64,
            max_pages_per_slot=8, prefill_buckets=(16, 32))
OPTS = {"temperature": 0.0, "num_predict": 12}


def _phase_counts() -> dict[str, int]:
    return {p: PHASE_SECONDS.count(model=MODEL, phase=p) for p in PHASES}


def _phase_sums() -> dict[str, float]:
    return {p: PHASE_SECONDS.sum(model=MODEL, phase=p) for p in PHASES}


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
    before = _phase_counts()
    launches0 = eng._gen
    _serve(eng, n=2)
    after = _phase_counts()
    for p in PHASES:
        assert after[p] > before[p], p
    # admit's count is admissions; dispatch_verify's is launches
    assert after["admit"] - before["admit"] == 2
    assert after["dispatch_verify"] - before["dispatch_verify"] == eng._gen - launches0
    assert set(eng.batch_state()["runnerPhaseSeconds"]) == set(PHASES)


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
    # the first launch reads the two prompts; the next, two tokens more
    # a slot (the prefill's sample and the block's own)
    assert seen[0] == sum(lens) and seen[1] == sum(lens) + 4
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
