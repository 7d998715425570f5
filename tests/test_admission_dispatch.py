"""Admission's device half (ISSUE 25, ISSUE 60): one admission launches the
wrapped entry points and the one `admit_seed` program (the sampler row and
the cached span's tail into the repeat-penalty window: one launch however
many chunks are cached), and nothing else — no eager one-element program per
scalar or per sampler field; the program writes exactly the admitted slot's
row at each field's dtype; a finished slot's `active` flag goes down in one
launch; the program has one signature; a warm re-ask under a repeat penalty
streams the cold ask's tokens; and a follower that replays the liaison's
plan records ends with the same sampler rows and active flags."""

import dataclasses

import numpy as np
import pytest
from jax._src import dispatch as jax_dispatch

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.engine.engine import _SEED_LAUNCHES
from gridllm_tpu.ops.sampling import SamplingParams

# chunk length 16 (two pages): a 40-token prompt is three chunks, and a
# re-ask of it finds its first pages in the prefix cache
TINY = dict(model="tiny-llama", max_slots=4, page_size=8, num_pages=64,
            max_pages_per_slot=8, prefill_buckets=(16, 32), prefill_chunk=16)
FIELDS = [f.name for f in dataclasses.fields(SamplingParams)]
GREEDY = {"temperature": 0.0, "num_predict": 4}


def _eager_dispatches() -> int:
    """Eager primitive dispatches so far in this process: every op run
    outside a jitted function (`x.at[i].set(v)`, `jnp.int32(3)`,
    `jnp.asarray([..])`) looks its one-primitive program up in this cache,
    hit or miss. Jitted calls fed numpy arguments never touch it."""
    info = jax_dispatch.xla_primitive_callable.cache_info()
    return info.hits + info.misses


def _count_launches(eng: InferenceEngine) -> list[str]:
    """Record the name of every wrapped entry point as it is called."""
    calls: list[str] = []
    for name, probe in eng.perf._probes.items():
        def counted(*a, _fn=probe._fn, _name=name, **kw):
            calls.append(_name)
            return _fn(*a, **kw)
        probe._fn = counted
    return calls


def _drain(eng: InferenceEngine) -> None:
    for _ in range(400):
        if not eng.step():
            return
    raise AssertionError("engine did not drain")


def _sampling(eng: InferenceEngine) -> dict[str, np.ndarray]:
    return {f: np.asarray(getattr(eng.sampling, f)) for f in FIELDS}


def test_the_hook_counts_eager_programs():
    """The counter the launch tests rely on sees what they guard against."""
    import jax.numpy as jnp

    x = jnp.zeros((4,), jnp.int32)
    n0 = _eager_dispatches()
    x = x.at[1].set(3)
    assert _eager_dispatches() - n0 >= 1
    n0 = _eager_dispatches()
    jnp.int32(7)
    assert _eager_dispatches() - n0 >= 1


@pytest.mark.parametrize("route", ["bucketed", "chunked_cold", "cache_warm",
                                   "cache_warm_long"])
def test_admission_launches_only_named_programs(route):
    eng = InferenceEngine(EngineConfig(**{
        **TINY, "max_pages_per_slot": 16}, prefix_cache=True))
    long_ids = [3 + (i % 50) for i in range(40)]
    chunk = "mixed_chunk" if eng._use_mixed else "prefill_chunk"
    if route == "bucketed":
        # a prompt one bucket would hold: one launch, of the mixed step
        # where the engine has one
        ids, expect = [5] * 10, [
            "admit_seed", "mixed_chunk" if eng._use_mixed else "prefill"]
    elif route == "chunked_cold":
        ids = long_ids
        expect = ["admit_seed"] + [chunk] * 3
    else:
        if route == "cache_warm_long":
            # six chunks and a half: the re-ask finds five or more cached
            long_ids = [3 + (i * 7 % 190) for i in range(100)]
        # the first ask leaves its full pages in the prefix cache
        eng.generate(GenerationRequest(id="cold", raw=True,
                                       prompt_ids=long_ids, options=GREEDY))
        ids = long_ids
        expect = None   # ONE admit_seed, then the uncached tail's chunks
    calls = _count_launches(eng)
    eng.submit(GenerationRequest(id="probe", raw=True, prompt_ids=ids,
                                 options=GREEDY))
    n0 = _eager_dispatches()
    seeds0 = _SEED_LAUNCHES.value(model=eng.cfg.name)
    assert eng._try_admit()
    eager = _eager_dispatches() - n0
    st = next(iter(eng._slots.values()))
    if expect is None:
        cached = st.cached_tokens
        c = eng._chunk_len
        floor = 5 * c if route == "cache_warm_long" else c
        assert cached >= floor, "the re-ask must hit the prefix cache"
        expect = ["admit_seed"] + [chunk] * (-(-(len(ids) - cached) // c))
    assert calls == expect
    # the stage's counter says the same: one jitted call, whatever is cached
    assert _SEED_LAUNCHES.value(model=eng.cfg.name) - seeds0 == 1
    assert eager == 0, f"{eager} eager one-off programs in one admission"
    _drain(eng)
    assert eng.perf.state()["admit_seed"]["signatures"] == 1


def test_sampler_row_written_at_dtype_and_other_rows_untouched():
    eng = InferenceEngine(EngineConfig(**TINY))
    before = _sampling(eng)
    opts = {"temperature": 0.7, "top_k": 17, "top_p": 0.55, "min_p": 0.05,
            "repeat_penalty": 1.3, "repeat_last_n": -1,
            "seed": (1 << 40) + 12345, "num_predict": 9}
    resume = [7, 8, 9]
    eng.submit(GenerationRequest(id="r", raw=True, prompt_ids=[5] * 10,
                                 options=opts, resume_ids=resume))
    assert eng._try_admit()
    (slot, st), = eng._slots.items()
    after = _sampling(eng)
    want = {
        "temperature": np.float32(0.7), "top_k": np.int32(17),
        "top_p": np.float32(0.55), "min_p": np.float32(0.05),
        "repeat_penalty": np.float32(1.3),
        # -1 → the request's context size, clamped to the window buffer
        "repeat_last_n": np.int32(min(st.capacity, eng.config.repeat_window)),
        "seed": np.int32(((1 << 40) + 12345) & 0x7FFFFFFF),
        # admitted at len(resume); the prefill's own sample took one draw
        "step": np.int32(len(resume) + 1),
    }
    for f in FIELDS:
        assert after[f].dtype == before[f].dtype == want[f].dtype, f
        assert after[f][slot] == want[f], f
        others = np.arange(eng.config.max_slots) != slot
        np.testing.assert_array_equal(after[f][others], before[f][others], f)
    _drain(eng)


def test_finished_slot_goes_inactive_in_one_launch():
    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=False))
    done: dict[str, bool] = {}
    for rid, n in (("short", 2), ("long", 40)):
        eng.submit(GenerationRequest(
            id=rid, raw=True, prompt_ids=[9] * 6,
            options={"temperature": 0.0, "num_predict": n},
            on_chunk=lambda d, fin, res, rid=rid: done.update({rid: fin})))
    while eng._try_admit():
        pass
    slot_of = {st.req.id: s for s, st in eng._slots.items()}
    calls = _count_launches(eng)
    eager = 0
    for _ in range(50):
        n0 = _eager_dispatches()
        eng.step()
        eager = _eager_dispatches() - n0
        if done.get("short"):
            break
    assert done.get("short") and not done.get("long")
    assert calls.count("deactivate") == 1
    assert eager == 0, "the step that finished a slot ran eager programs"
    active = np.asarray(eng.active)
    assert not active[slot_of["short"]] and active[slot_of["long"]]
    assert not active[[s for s in range(4) if s not in slot_of.values()]].any()
    _drain(eng)
    assert not np.asarray(eng.active).any()


def test_row_program_has_one_signature():
    eng = InferenceEngine(EngineConfig(**TINY))
    mixes = [
        {"temperature": 0, "top_k": 1, "seed": 3},            # ints for floats
        {"temperature": 0.9, "top_p": 1, "min_p": 0.1},
        {"temperature": 0.0, "repeat_last_n": 0, "repeat_penalty": 1},
        {"temperature": 1.5, "top_k": 0, "seed": 2**31 + 5},
    ]
    for i, o in enumerate(mixes):
        res = eng.generate(GenerationRequest(
            id=f"m{i}", raw=True, prompt_ids=[4 + i] * (6 + i),
            options={**o, "num_predict": 3}))
        assert res.done_reason == "length"
    state = eng.perf.state()
    assert state["admit_seed"]["compiles"] == 1
    assert state["deactivate"]["compiles"] == 1
    assert all(p["steadyRecompiles"] == 0 for p in state.values()), state


@pytest.mark.parametrize("last_n", [8, 64, -1])
def test_warm_reask_under_a_repeat_penalty_streams_the_cold_tokens(last_n):
    """The window a cached admission seeds from the span's tail is the one
    the cold ask built through the model, so a penalised stream cannot tell
    them apart: the warm re-ask's tokens are the cold ask's, and an engine
    with no prefix cache gives both."""
    kw = {**TINY, "max_pages_per_slot": 16}
    ids = [3 + (i * 7 % 23) for i in range(100)]    # tokens repeat: it bites
    opts = {"temperature": 0.0, "repeat_penalty": 1.3,
            "repeat_last_n": last_n, "num_predict": 12}

    def ask(eng, rid):
        return eng.generate(GenerationRequest(
            id=rid, raw=True, prompt_ids=ids, options=opts))

    eng = InferenceEngine(EngineConfig(**kw, prefix_cache=True))
    cold, warm = ask(eng, "cold"), ask(eng, "warm")
    assert cold.cached_tokens == 0
    assert warm.cached_tokens >= 5 * eng._chunk_len
    assert warm.token_ids == cold.token_ids
    plain = ask(InferenceEngine(EngineConfig(**kw, prefix_cache=False)), "p")
    assert plain.cached_tokens == 0 and plain.token_ids == cold.token_ids
    # the penalty is live: without it the same prompt streams other tokens
    free = eng.generate(GenerationRequest(
        id="free", raw=True, prompt_ids=ids,
        options={**opts, "repeat_penalty": 1.0}))
    assert free.token_ids != cold.token_ids


def test_follower_replay_matches_sampler_rows_and_active_flags():
    """In-process plan replay: the record format is what it was (followers
    go through the same _dispatch_prefill / deactivate program), and after
    every `admit` and `deact` record the follower's sampler rows and active
    flags are the liaison's."""
    kw = dict(**TINY, prefix_cache=True)
    liaison = InferenceEngine(EngineConfig(**kw))
    follower = InferenceEngine(EngineConfig(**kw))
    records: list[dict] = []
    liaison.plan_sink = records.append
    long_ids = [3 + (i % 50) for i in range(40)]
    asks = [
        ([5] * 10, {"temperature": 0.0, "top_k": 3, "seed": 11}),
        (long_ids, {"temperature": 0.6, "top_p": 0.7, "min_p": 0.02,
                    "repeat_penalty": 1.2, "repeat_last_n": 32, "seed": 5}),
        (long_ids, {"temperature": 0.0, "repeat_last_n": -1}),   # cache hit
    ]
    for i, (ids, o) in enumerate(asks):
        res = liaison.generate(GenerationRequest(
            id=f"q{i}", raw=True, prompt_ids=ids,
            options={**o, "num_predict": 4}))
        assert res.done_reason in ("stop", "length")
    admits = [r for r in records if r["op"] == "admit"]
    deacts = [r for r in records if r["op"] == "deact"]
    assert len(admits) == len(deacts) == 3
    assert admits[2]["cached"] > 0
    for rec in admits:
        assert set(rec) == {"op", "slot", "ids", "row", "sp", "cached"}
        assert set(rec["sp"]) == set(FIELDS)
    for rec in deacts:
        assert set(rec) == {"op", "slot"}

    for rec in records:
        follower.apply_plan_op(rec)
        if rec["op"] == "admit":
            got, slot = _sampling(follower), rec["slot"]
            for f in FIELDS:
                # the prefill's own sample has taken one draw
                assert got[f][slot] == np.asarray(
                    rec["sp"][f], got[f].dtype) + (f == "step"), f
        elif rec["op"] == "deact":
            assert not np.asarray(follower.active)[rec["slot"]]
    want, got = _sampling(liaison), _sampling(follower)
    for f in FIELDS:
        np.testing.assert_array_equal(got[f], want[f], f)
    np.testing.assert_array_equal(np.asarray(follower.active),
                                  np.asarray(liaison.active))
    np.testing.assert_array_equal(np.asarray(follower.tokens),
                                  np.asarray(liaison.tokens))
