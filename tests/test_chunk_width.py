"""The widths of a chunk launch. A prompt's last chunk behind a prefix runs
at the narrow width where that holds it (ISSUE 32), and every engine with a
mixed step admits every prompt through it, a first chunk that holds its
whole prompt at a width of its own (ISSUE 39): the one helper that chooses;
the same greedy tokens and sampler window as an engine with one width and
as one that admits through bucketed prefill; every width compiled by the
requests ``prewarm()`` sends, so no later length compiles; a follower picks
the liaison's widths from the admit record; an image prompt keeps the full
width; a running stream gets a token from the launch that admits a short
prompt; and the counters that say which width each admission took."""

import base64
import io

import numpy as np
import pytest

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.engine import engine as engine_module
from gridllm_tpu.engine.engine import (_CHUNK_LAUNCHES, _CHUNK_TOKENS,
                                       _SEED_TAIL)
from gridllm_tpu.obs.perf import XLA_COMPILE_SECONDS

# pages of 8, chunks of 32, the narrow width 16: a 70-token prompt is two
# full chunks and a 6-token last one; its re-ask finds 64 tokens cached
TINY = dict(model="tiny-llama", max_slots=4, page_size=8, num_pages=96,
            max_pages_per_slot=16, prefill_buckets=(16, 32), prefill_chunk=32)
LADDER = dict(**TINY, prefill_chunk_narrow=16)
ONE_WIDTH = dict(**TINY, prefill_chunk_narrow=32)
GREEDY = {"temperature": 0.0, "num_predict": 6}
# the shipped first-chunk width is no narrower than a tiny chunk: a test
# that wants it between the narrow width and the chunk builds with this one
FIRST = 24
# admission's one host record: slot, tail length, the sampler row, the tail
SEED_RECORD = _SEED_TAIL + EngineConfig(**TINY).repeat_window


def build(kw: dict, first: int | None = None, **more) -> InferenceEngine:
    """An engine of `kw`; with `first`, one whose first chunk runs `first`
    wide where that holds the prompt (the module's width is read once, at
    construction)."""
    shipped = engine_module.FIRST_CHUNK
    if first is not None:
        engine_module.FIRST_CHUNK = first
    try:
        return InferenceEngine(EngineConfig(**{**kw, **more}))
    finally:
        engine_module.FIRST_CHUNK = shipped


def bucketed(eng: InferenceEngine) -> InferenceEngine:
    """The same engine admitting as one without a mixed step does: bucketed
    prefill for a prompt up to a chunk, ``prefill_chunk`` beyond."""
    eng._use_mixed = False
    return eng


def ids(n: int, salt: int = 0) -> list[int]:
    return [3 + (salt + 7 * i) % 200 for i in range(n)]


CHUNKS = ("mixed_chunk", "prefill_chunk")
# with the admission's one seed launch, recorded at its host record's width
WITH_SEED = CHUNKS + ("admit_seed",)


def chunk_widths(eng: InferenceEngine, names=CHUNKS) -> list[tuple[str, int]]:
    """Record (program, chunk width) of every chunk-shaped launch: the
    chunk programs; or of ``names`` (``prefill``: the bucket;
    ``admit_seed``: its one host record, the slot's row and the cached
    span's tail at ``repeat_window``)."""
    seen: list[tuple[str, int]] = []
    for name in names:
        probe = eng.perf._probes.get(name)
        if probe is None:
            continue
        at = 4 if name == "admit_seed" else 1

        def counted(*a, _fn=probe._fn, _name=name, _at=at, **kw):
            seen.append((_name, int(a[_at].shape[0])))
            return _fn(*a, **kw)
        probe._fn = counted
    return seen


def model_launches(seen) -> list[int]:
    return [w for name, w in seen if name != "admit_seed"]


def drain(eng: InferenceEngine) -> None:
    for _ in range(400):
        if not eng.step():
            return
    raise AssertionError("engine did not drain")


@pytest.mark.parametrize("chunk,narrow,page,want_narrow", [
    (32, 16, 8, 16),      # the ladder
    (32, 20, 8, 16),      # page-aligned, like the chunk itself
    (32, 4, 8, 8),        # never under a page
    (16, 16, 8, None),    # the chunk is no wider: one entry
    (16, 256, 8, None),   # every tiny preset of the suite: as before
])
def test_chunk_width_over_every_last_chunk_length(chunk, narrow, page, want_narrow):
    eng = InferenceEngine(EngineConfig(**{
        **TINY, "page_size": page, "prefill_chunk": chunk,
        "prefill_chunk_narrow": narrow}))
    c = eng._chunk_len
    assert c == chunk
    # behind a prefix, whatever its length
    got = {n: eng._chunk_width(n, c) for n in range(1, c + 1)}
    assert got == {n: eng._chunk_width(n, 3 * c) for n in got}
    # the shipped first-chunk width is the tiny chunk: one width up front
    assert {eng._chunk_width(n, 0) for n in range(1, 3 * c)} == {c}
    if want_narrow is None:
        assert set(got.values()) == {c}
        return
    assert eng._chunk_narrow == want_narrow and want_narrow % page == 0
    for n, w in got.items():
        assert w == (want_narrow if n <= want_narrow else c), n
        assert w >= n


@pytest.mark.parametrize("first,want_first", [
    (24, 24),     # between the narrow width and the chunk
    (28, 24),     # page-aligned, like the chunk itself
    (8, 8),       # narrower than the narrow width: its own rule still
    (32, None),   # the chunk is no wider: one width up front
    (512, None),  # the shipped width over a tiny chunk
])
def test_chunk_width_over_every_first_chunk_length(first, want_first):
    eng = build(LADDER, first=first)
    c = eng._chunk_len
    got = {n: eng._chunk_width(n, 0) for n in range(1, 2 * c + 2)}
    for n, w in got.items():
        assert w == (want_first if want_first and n <= want_first else c), n
        assert w >= min(n, c)
    # a first chunk's width is no rung behind a prefix, and the reverse
    assert {eng._chunk_width(n, c) for n in range(1, c + 1)} == {16, c}


def test_shipped_widths():
    cfg = EngineConfig(model="tiny-llama")
    assert (cfg.prefill_chunk, cfg.prefill_chunk_narrow) == (1024, 256)
    assert engine_module.FIRST_CHUNK == 512       # the width the chip read
    assert engine_module.ROUTED_CHUNK == 512


def test_the_model_picks_the_widths_and_no_name_does():
    """What the engine observes of its model decides: a routed family has
    one width however the ladder is set, a dense one the three."""
    kw = {**LADDER, "max_slots": 2}
    dense = build(kw, first=FIRST)
    routed = build({**kw, "model": "tiny-smallthinker"}, first=FIRST)
    assert dense._use_mixed and routed._use_mixed
    assert (dense._chunk_first, dense._chunk_narrow, dense._chunk_len) == (24, 16, 32)
    assert (routed._chunk_first, routed._chunk_narrow, routed._chunk_len) == (32, 32, 32)
    assert not hasattr(dense, "_admit_mixed")


@pytest.mark.parametrize("case", ["cold_multi_chunk", "cached_reask",
                                  "multiple_of_the_chunk", "fits_one_bucket",
                                  "past_the_first_width"])
def test_tokens_and_window_match_a_one_width_engine(case):
    n = {"cold_multi_chunk": 70, "cached_reask": 70,
         "multiple_of_the_chunk": 64, "fits_one_bucket": 20,
         "past_the_first_width": 27}[case]
    prompt = ids(n, salt=len(case))
    out = {}
    for kind, kw, first in (("ladder", LADDER, FIRST), ("one", ONE_WIDTH, None)):
        eng = build(kw, first=first, prefix_cache=True)
        if case == "cached_reask":
            eng.generate(GenerationRequest(
                id="first", raw=True, prompt_ids=prompt, options=GREEDY))
        seen = chunk_widths(eng, WITH_SEED)
        eng.submit(GenerationRequest(id=case, raw=True, prompt_ids=prompt,
                                     options={**GREEDY, "repeat_last_n": 48}))
        assert eng._try_admit()
        (slot, st), = eng._slots.items()
        state = [np.asarray(x)[slot].copy()
                 for x in (eng.window, eng.wlen, eng.counts)]
        drain(eng)
        assert st.cached_tokens == (64 if case == "cached_reask" else 0)
        # one seed launch, two cached chunks or none
        assert seen[0] == ("admit_seed", SEED_RECORD)
        assert len(seen) == 1 + len(model_launches(seen))
        out[kind] = (list(st.generated), state, model_launches(seen))
    (tok_l, state_l, w_l), (tok_o, state_o, w_o) = out["ladder"], out["one"]
    assert tok_l == tok_o and len(tok_l) == 6
    for a, b in zip(state_l, state_o):
        np.testing.assert_array_equal(a, b)
    assert w_l == {"cold_multi_chunk": [32, 32, 16], "cached_reask": [16],
                   "multiple_of_the_chunk": [32, 32],
                   "fits_one_bucket": [24],
                   "past_the_first_width": [32]}[case]
    assert set(w_o) <= {32} and len(w_o) == len(w_l)


# both sides of every width boundary: the first width (24), the chunk (32),
# a chunk and the narrow width (48), two chunks (64)
@pytest.mark.parametrize("n", [1, 24, 25, 32, 33, 48, 49, 64, 65])
def test_tokens_and_window_match_the_bucketed_engine(n):
    """Admission through the mixed step serves what bucketed prefill (and
    ``prefill_chunk`` past a chunk) served: the same greedy tokens under the
    repeat penalty, the same sampler window, and no ``prefill`` launch."""
    prompt = ids(n, salt=n)
    out = {}
    for kind in ("mixed", "bucketed"):
        eng = build(LADDER, first=FIRST)
        if kind == "bucketed":
            bucketed(eng)
        prefills = chunk_widths(eng, ("prefill",))
        seen = chunk_widths(eng)
        eng.submit(GenerationRequest(id=f"b{n}", raw=True, prompt_ids=prompt,
                                     options={**GREEDY, "repeat_last_n": 48}))
        assert eng._try_admit()
        (slot, st), = eng._slots.items()
        drain(eng)
        state = [np.asarray(x)[slot].copy()
                 for x in (eng.window, eng.wlen, eng.counts)]
        out[kind] = (list(st.generated), state, prefills, seen)
    (tok_m, state_m, pre_m, seen_m), (tok_b, state_b, pre_b, seen_b) = (
        out["mixed"], out["bucketed"])
    assert tok_m == tok_b and len(tok_m) == 6
    for a, b in zip(state_m, state_b):
        np.testing.assert_array_equal(a, b)
    assert pre_m == [] and {name for name, _ in seen_m} == {"mixed_chunk"}
    assert [w for _, w in pre_b] == (
        [32] if 16 < n <= 32 else [16] if n <= 16 else [])
    assert {name for name, _ in seen_b} <= {"prefill_chunk"}


def test_a_dense_engine_never_calls_prefill_for_a_text_prompt():
    """Every length from one token to two chunks and one goes through the
    mixed step, at the width the helper names, and none through ``prefill``."""
    eng = build(LADDER, first=FIRST)
    called = chunk_widths(eng, ("prefill",))
    seen = chunk_widths(eng)
    opts = {"temperature": 0.0, "num_predict": 2}
    for n in range(1, 2 * eng._chunk_len + 2):
        del seen[:]
        res = eng.generate(GenerationRequest(
            id=f"n{n}", raw=True, prompt_ids=ids(n, salt=3 * n), options=opts))
        assert res.done_reason in ("stop", "length"), (n, res.error)
        want = [32] * ((n - 1) // 32)
        want.append((24 if n <= 24 else 32) if not want
                    else (16 if n - 32 * len(want) <= 16 else 32))
        assert seen == [("mixed_chunk", w) for w in want], n
    assert called == []


def test_running_streams_get_a_token_from_the_launch_that_admits():
    """The launch that admits a short prompt carries a decode row for every
    running stream: the stream's next token is in that launch's block, where
    a prefill of its own would leave it waiting for the launch after; and
    the admitted prompt's first token is in the same block, not the next."""
    eng = build(LADDER, first=FIRST)
    eng.submit(GenerationRequest(id="running", raw=True, prompt_ids=ids(40),
                                 options={"temperature": 0.0, "num_predict": 30}))
    for _ in range(3):
        assert eng.step()
    (slot_a, st_a), = eng._slots.items()
    had = len(st_a.generated)
    seen = chunk_widths(eng)
    eng.submit(GenerationRequest(id="short", raw=True, prompt_ids=ids(9, 5),
                                 options=GREEDY))
    assert eng._try_admit()
    assert seen == [("mixed_chunk", 24)] and len(eng._inflight) == 1
    eng._fetch_oldest()
    assert len(st_a.generated) == had + 1
    st_b = next(st for s, st in eng._slots.items() if s != slot_a)
    assert len(st_b.generated) == 1 and st_b.t_prefill_ns > 0
    eng.step()
    assert len(st_b.generated) >= 2 and len(st_a.generated) >= had + 2
    drain(eng)
    # the bucketed engine serves the same first token one launch later
    other = bucketed(build(LADDER, first=FIRST))
    other.submit(GenerationRequest(id="short", raw=True, prompt_ids=ids(9, 5),
                                   options=GREEDY))
    assert other._try_admit() and not other._inflight
    (st_o,) = other._slots.values()
    assert st_o.generated == []
    other.step()
    assert st_o.generated[0] == st_b.generated[0]
    drain(other)


def test_an_empty_raw_prompt_is_its_bos():
    """An admission is at least one launch: a prompt of no tokens is served
    as its BOS alone, by a dense and a routed family alike."""
    for model in ("tiny-llama", "tiny-smallthinker"):
        eng = build({**LADDER, "model": model, "max_slots": 2})
        res = eng.generate(GenerationRequest(
            id="empty", raw=True, prompt="", options=GREEDY))
        assert res.done_reason == "length" and res.prompt_eval_count == 1
        assert len(res.token_ids) == 6


def test_prewarm_compiles_every_width_with_three_requests():
    eng = build(LADDER, first=FIRST, prefix_cache=True)
    seen = chunk_widths(eng, WITH_SEED)
    sent: list[int] = []
    generate = eng.generate

    def counting(req):
        sent.append(len(req.prompt_ids))
        return generate(req)
    eng.generate = counting
    name = eng.cfg.name
    before = {w: _CHUNK_LAUNCHES.value(model=name, width=w) for w in ("16", "32")}
    eng.prewarm()
    eng.generate = generate
    # a first chunk's width, then chunk + 1 twice: no bucket
    assert sent == [24, 33, 33]
    # each admission's one seed launch; the first width; a full chunk and a
    # one-token last chunk, cold; then the cached prefix's tail in the seed
    # and the same one token behind it
    seed = ("admit_seed", SEED_RECORD)
    assert seen == [seed, ("mixed_chunk", 24),
                    seed, ("mixed_chunk", 32), ("mixed_chunk", 16),
                    seed, ("mixed_chunk", 16)]
    assert _CHUNK_LAUNCHES.value(model=name, width="32") - before["32"] == 1
    assert _CHUNK_LAUNCHES.value(model=name, width="16") - before["16"] == 2
    state = eng.perf.state()
    assert state["mixed_chunk"]["signatures"] == 3
    assert state["admit_seed"]["signatures"] == 1
    assert state["prefill"]["signatures"] == 0


@pytest.mark.parametrize("kind,want", [
    # no mixed step: each bucket up to the chunk, then chunk + 1 twice
    ("bucketed", [16, 32, 33, 33]),
    # the shipped first width over a tiny chunk: the chunk is the one width
    ("one_first_width", [32, 33, 33]),
    # a context that ends inside the chunk: the full width by its own request
    ("short_context", [24, 30]),
    # a routed family: its one width, as before
    ("routed", [32, 33, 33]),
])
def test_prewarm_sends_what_admission_can_launch(kind, want):
    kw = dict(LADDER, prefix_cache=True)
    if kind == "routed":
        kw.update(model="tiny-smallthinker", max_slots=2)
    if kind == "short_context":
        kw.update(max_pages_per_slot=4)      # 32 positions a request
    eng = build(kw, first=None if kind == "one_first_width" else FIRST)
    if kind == "bucketed":
        bucketed(eng)
    sent: list[int] = []
    generate = eng.generate
    eng.generate = lambda req: sent.append(len(req.prompt_ids)) or generate(req)
    eng.prewarm()
    assert sent == want


def test_prewarm_pauses_the_collector_and_leaves_it_as_it_was():
    import gc

    eng = InferenceEngine(EngineConfig(**LADDER, prefix_cache=True))
    during: list[bool] = []
    generate = eng.generate

    def watching(req):
        during.append(gc.isenabled())
        return generate(req)
    eng.generate = watching
    assert gc.isenabled()
    eng.prewarm()
    assert during and not any(during) and gc.isenabled()
    # a caller that runs with the collector off keeps it off
    other = InferenceEngine(EngineConfig(**ONE_WIDTH))
    gc.disable()
    try:
        other.prewarm()
        assert not gc.isenabled()
    finally:
        gc.enable()


def test_a_width_costs_prewarm_one_executable():
    """What jax builds (or loads from its cache) in prewarm is the one-width
    engine's count and one for each further width: the narrow chunk program
    once, the first chunk's once; and an engine that admits through the
    mixed step builds fewer than one that keeps its two buckets beside it."""
    built = []
    # the first engine also pays what the process builds once
    for kw, first in ((ONE_WIDTH, None), (LADDER, None), (ONE_WIDTH, None),
                      (LADDER, FIRST)):
        eng = build(kw, first=first, prefix_cache=True)
        n0 = XLA_COMPILE_SECONDS.count(model=eng.cfg.name)
        eng.prewarm()
        built.append(XLA_COMPILE_SECONDS.count(model=eng.cfg.name) - n0)
    assert built[1] == built[2] + 1 and built[3] == built[2] + 2, built
    eng = bucketed(build(LADDER, prefix_cache=True))
    n0 = XLA_COMPILE_SECONDS.count(model=eng.cfg.name)
    eng.prewarm()
    # prefill at 16 and 32 and prefill_chunk at 32 and 16, where the mixed
    # engine builds mixed_chunk at 32 and 16
    assert XLA_COMPILE_SECONDS.count(model=eng.cfg.name) - n0 == built[1] + 2


def test_after_prewarm_no_length_compiles():
    """Every length from 1 to three chunks and one, cold and then as a
    prefix-cache hit, finds its programs built: no new signature of any
    wrapped entry point and no executable built or loaded by jax."""
    eng = build(LADDER, first=FIRST, prefix_cache=True)
    eng.prewarm()
    opts = {"temperature": 0.0, "num_predict": 2}
    # the first real request arms the tripwire: a new signature after it
    # would also count as a steady-state recompile
    eng.generate(GenerationRequest(id="arm", raw=True, prompt_ids=ids(5, 1),
                                   options=opts))
    name = eng.cfg.name
    signatures = {k: v["signatures"] for k, v in eng.perf.state().items()}
    built = XLA_COMPILE_SECONDS.count(model=name)
    seen = chunk_widths(eng)
    hits = 0
    for n in range(1, 3 * eng._chunk_len + 2):
        for again in (False, True):
            res = eng.generate(GenerationRequest(
                id=f"n{n}-{again}", raw=True, prompt_ids=ids(n, salt=n),
                options=opts))
            assert res.done_reason in ("stop", "length"), (n, res.error)
            hits += res.cached_tokens > 0
    state = eng.perf.state()
    assert {k: v["signatures"] for k, v in state.items()} == signatures
    assert all(v["steadyRecompiles"] == 0 for v in state.values()), state
    assert XLA_COMPILE_SECONDS.count(model=name) == built
    assert hits >= 3 * eng._chunk_len - eng.config.page_size
    assert set(model_launches(seen)) == {16, 24, 32}
    assert state["prefill"]["signatures"] == 0


def test_follower_replays_the_liaisons_widths():
    liaison = build(LADDER, first=FIRST, prefix_cache=True)
    follower = build(LADDER, first=FIRST, prefix_cache=True)
    records: list[dict] = []
    liaison.plan_sink = records.append
    led, followed = (chunk_widths(liaison, WITH_SEED),
                     chunk_widths(follower, WITH_SEED))
    # cold with a short tail, its re-ask, a tail too long for the narrow
    # width, a prompt that ends on a chunk boundary, one the first width
    # holds and one it does not
    for i, n in enumerate((70, 70, 90, 64, 20, 27)):
        res = liaison.generate(GenerationRequest(
            id=f"q{i}", raw=True, prompt_ids=ids(n, salt=n), options=GREEDY))
        assert res.done_reason in ("stop", "length")
    admits = [r for r in records if r["op"] == "admit"]
    assert [r["cached"] for r in admits] == [0, 64, 0, 0, 0, 0]
    for rec in records:
        follower.apply_plan_op(rec)
    assert followed == led
    assert model_launches(led) == [32, 32, 16, 16, 32, 32, 32, 32, 32, 24, 32]
    assert len(led) - len(model_launches(led)) == len(admits)   # a seed each
    np.testing.assert_array_equal(np.asarray(follower.tokens),
                                  np.asarray(liaison.tokens))
    np.testing.assert_array_equal(np.asarray(follower.window),
                                  np.asarray(liaison.window))


def test_a_pp_engine_picks_the_same_widths_on_prefill_chunk():
    """Pipeline engines admit chunk by chunk through prefill_chunk_fn (no
    mixed step): the same helper, the same widths, the one-width tokens;
    and a prompt that fits a bucket through bucketed prefill, as before."""
    from gridllm_tpu.parallel.mesh import MeshConfig

    out = []
    for narrow in (16, 32):
        eng = build({**TINY, "max_slots": 2, "prefill_chunk_narrow": narrow,
                     "mesh": MeshConfig(pp=2, dp=2, tp=2)}, first=FIRST)
        assert not eng._use_mixed
        seen = chunk_widths(eng)
        res = eng.generate(GenerationRequest(
            id="pp", raw=True, prompt_ids=ids(70, salt=9), options=GREEDY))
        assert res.done_reason in ("stop", "length"), res.error
        out.append((res.token_ids, list(seen)))
        del seen[:]
        short = eng.generate(GenerationRequest(
            id="pp-short", raw=True, prompt_ids=ids(20, salt=2), options=GREEDY))
        assert short.done_reason in ("stop", "length"), short.error
        assert seen == [] and eng.perf.state()["prefill"]["signatures"] == 1
    (tok_l, seen_l), (tok_o, seen_o) = out
    assert tok_l == tok_o and len(tok_l) == 6
    assert seen_l == [("prefill_chunk", 32), ("prefill_chunk", 32),
                      ("prefill_chunk", 16)]
    assert seen_o == [("prefill_chunk", 32)] * 3


def test_an_sp_engine_admits_whole_prompts_through_prefill():
    """Ring attention prefills whole prompts: no chunked path, so no mixed
    step, and every prompt pads to its bucket as before."""
    from gridllm_tpu.parallel.mesh import MeshConfig

    eng = build({**TINY, "max_slots": 2, "prefill_buckets": (16, 32, 64),
                 "mesh": MeshConfig(sp=2, tp=4)}, first=FIRST)
    assert not eng._use_chunked and not eng._use_mixed
    seen = chunk_widths(eng)
    for n in (9, 20, 40):
        res = eng.generate(GenerationRequest(
            id=f"sp{n}", raw=True, prompt_ids=ids(n, salt=n), options=GREEDY))
        assert res.done_reason in ("stop", "length"), res.error
    assert seen == [] and eng.perf.state()["prefill"]["signatures"] == 3


def test_counters_say_which_width_each_admission_took():
    eng = build(LADDER, first=FIRST, prefix_cache=True)
    name = eng.cfg.name

    def read():
        return ({w: _CHUNK_LAUNCHES.value(model=name, width=w)
                 for w in ("16", "24", "32")},
                {k: _CHUNK_TOKENS.value(model=name, kind=k)
                 for k in ("real", "padded")})
    l0, t0 = read()
    prompt = ids(70, salt=3)
    for i in range(2):       # cold: 32 + 32 + 6 in 16; the re-ask: 6 in 16
        eng.generate(GenerationRequest(id=f"c{i}", raw=True,
                                       prompt_ids=prompt, options=GREEDY))
    eng.generate(GenerationRequest(id="short", raw=True, prompt_ids=ids(9),
                                   options=GREEDY))    # the first width
    eng.generate(GenerationRequest(id="mid", raw=True, prompt_ids=ids(27, 1),
                                   options=GREEDY))    # past it: a chunk
    l1, t1 = read()
    assert {w: l1[w] - l0[w] for w in l1} == {"16": 2, "24": 1, "32": 3}
    assert t1["real"] - t0["real"] == 70 + 6 + 9 + 27
    assert t1["padded"] - t0["padded"] == 32 + 32 + 16 + 16 + 24 + 32
    from gridllm_tpu.obs import default_registry

    text = default_registry().render()
    assert f'gridllm_engine_chunk_launches_total{{model="{name}",width="16"}}' in text
    assert f'gridllm_engine_chunk_tokens_total{{model="{name}",kind="padded"}}' in text


def test_an_image_prompt_keeps_the_full_width():
    Image = pytest.importorskip("PIL.Image")
    img = Image.fromarray(
        np.random.default_rng(4).integers(0, 255, (30, 30, 3), np.uint8))
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    b64 = base64.b64encode(buf.getvalue()).decode()
    eng = InferenceEngine(EngineConfig(
        model="tiny-llava", max_slots=2, page_size=8, num_pages=64,
        max_pages_per_slot=8, prefill_buckets=(16, 32), prefill_chunk=16,
        prefill_chunk_narrow=8))
    assert (eng._chunk_len, eng._chunk_narrow) == (16, 8)
    seen = chunk_widths(eng)
    opts = {"temperature": 0, "num_predict": 3, "seed": 1}
    # BOS + 4 patches + 14 bytes = 19 tokens: a full chunk and 3 more
    res = eng.generate(GenerationRequest(
        id="img", prompt="x" * 14, images=[b64], options=opts))
    assert res.done_reason in ("stop", "length") and res.prompt_eval_count == 19
    assert model_launches(seen) == [16, 16]
    assert eng.perf.state()["splice_embeds"]["signatures"] == 1
    # one that a chunk holds rides the mixed step too, at the same width
    del seen[:]
    res = eng.generate(GenerationRequest(
        id="img-short", prompt="x" * 3, images=[b64], options=opts))
    assert res.done_reason in ("stop", "length") and res.prompt_eval_count == 8
    assert model_launches(seen) == [16]
    assert eng.perf.state()["splice_embeds"]["signatures"] == 1
    assert eng.perf.state()["prefill"]["signatures"] == 0
    # the same length as text runs its tail at the narrow width
    del seen[:]
    eng.generate(GenerationRequest(
        id="txt", raw=True, prompt_ids=ids(19), options=opts))
    assert model_launches(seen) == [16, 8]
