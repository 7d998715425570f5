"""A prompt's last chunk runs at the narrow width where that holds it
(ISSUE 32): the one helper that chooses; the same greedy tokens and sampler
window as an engine with one width; both widths compiled by the requests
``prewarm()`` already sent, so no later length compiles; a follower picks
the liaison's widths from the admit record; an image prompt keeps the full
width; and the counters that say how often the narrow width engages."""

import base64
import io

import numpy as np
import pytest

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.engine.engine import _CHUNK_LAUNCHES, _CHUNK_TOKENS
from gridllm_tpu.obs.perf import XLA_COMPILE_SECONDS

# pages of 8, chunks of 32, the narrow width 16: a 70-token prompt is two
# full chunks and a 6-token last one; its re-ask finds 64 tokens cached
TINY = dict(model="tiny-llama", max_slots=4, page_size=8, num_pages=96,
            max_pages_per_slot=16, prefill_buckets=(16, 32), prefill_chunk=32)
LADDER = dict(**TINY, prefill_chunk_narrow=16)
ONE_WIDTH = dict(**TINY, prefill_chunk_narrow=32)
GREEDY = {"temperature": 0.0, "num_predict": 6}


def ids(n: int, salt: int = 0) -> list[int]:
    return [3 + (salt + 7 * i) % 200 for i in range(n)]


def chunk_widths(eng: InferenceEngine) -> list[tuple[str, int]]:
    """Record (program, chunk width) of every chunk-shaped launch: the
    chunk programs and ``window_seed``."""
    seen: list[tuple[str, int]] = []
    for name in ("mixed_chunk", "prefill_chunk", "window_seed"):
        probe = eng.perf._probes.get(name)
        if probe is None:
            continue
        at = 4 if name == "window_seed" else 1

        def counted(*a, _fn=probe._fn, _name=name, _at=at, **kw):
            seen.append((_name, int(a[_at].shape[0])))
            return _fn(*a, **kw)
        probe._fn = counted
    return seen


def model_launches(seen) -> list[int]:
    return [w for name, w in seen if name != "window_seed"]


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
    got = {n: eng._chunk_width(n) for n in range(1, c + 1)}
    if want_narrow is None:
        assert set(got.values()) == {c}
        return
    assert eng._chunk_narrow == want_narrow and want_narrow % page == 0
    for n, w in got.items():
        assert w == (want_narrow if n <= want_narrow else c), n
        assert w >= n


def test_shipped_widths():
    cfg = EngineConfig(model="tiny-llama")
    assert (cfg.prefill_chunk, cfg.prefill_chunk_narrow) == (1024, 256)


@pytest.mark.parametrize("case", ["cold_multi_chunk", "cached_reask",
                                  "multiple_of_the_chunk", "fits_one_bucket"])
def test_tokens_and_window_match_a_one_width_engine(case):
    n = {"cold_multi_chunk": 70, "cached_reask": 70,
         "multiple_of_the_chunk": 64, "fits_one_bucket": 20}[case]
    prompt = ids(n, salt=len(case))
    out = {}
    for kind, kw in (("ladder", LADDER), ("one", ONE_WIDTH)):
        eng = InferenceEngine(EngineConfig(**kw, prefix_cache=True))
        if case == "cached_reask":
            eng.generate(GenerationRequest(
                id="first", raw=True, prompt_ids=prompt, options=GREEDY))
        seen = chunk_widths(eng)
        eng.submit(GenerationRequest(id=case, raw=True, prompt_ids=prompt,
                                     options={**GREEDY, "repeat_last_n": 48}))
        assert eng._try_admit()
        (slot, st), = eng._slots.items()
        state = [np.asarray(x)[slot].copy()
                 for x in (eng.window, eng.wlen, eng.counts)]
        drain(eng)
        assert st.cached_tokens == (64 if case == "cached_reask" else 0)
        out[kind] = (list(st.generated), state, model_launches(seen))
    (tok_l, state_l, w_l), (tok_o, state_o, w_o) = out["ladder"], out["one"]
    assert tok_l == tok_o and len(tok_l) == 6
    for a, b in zip(state_l, state_o):
        np.testing.assert_array_equal(a, b)
    assert w_l == {"cold_multi_chunk": [32, 32, 16], "cached_reask": [16],
                   "multiple_of_the_chunk": [32, 32],
                   "fits_one_bucket": []}[case]
    assert set(w_o) <= {32} and len(w_o) == len(w_l)


def test_prewarm_compiles_both_widths_with_the_parents_requests():
    eng = InferenceEngine(EngineConfig(**LADDER, prefix_cache=True))
    seen = chunk_widths(eng)
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
    # the parent's list: each bucket up to the chunk, then chunk + 1 twice
    assert sent == [16, 32, 33, 33]
    # a full chunk and a one-token last chunk, cold; then the cached prefix
    # through window_seed and the same one token behind it
    assert seen == [("mixed_chunk", 32), ("mixed_chunk", 16),
                    ("window_seed", 32), ("mixed_chunk", 16)]
    assert _CHUNK_LAUNCHES.value(model=name, width="32") - before["32"] == 1
    assert _CHUNK_LAUNCHES.value(model=name, width="16") - before["16"] == 2
    state = eng.perf.state()
    assert state["mixed_chunk"]["signatures"] == 2
    assert state["window_seed"]["signatures"] == 1


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


def test_the_narrow_width_costs_prewarm_one_executable():
    """What jax builds (or loads from its cache) in prewarm is the one-width
    engine's count and one: the narrow chunk program, once."""
    built = []
    # the first engine also pays what the process builds once
    for kw in (ONE_WIDTH, LADDER, ONE_WIDTH):
        eng = InferenceEngine(EngineConfig(**kw, prefix_cache=True))
        n0 = XLA_COMPILE_SECONDS.count(model=eng.cfg.name)
        eng.prewarm()
        built.append(XLA_COMPILE_SECONDS.count(model=eng.cfg.name) - n0)
    assert built[1] == built[2] + 1, built


def test_after_prewarm_no_length_compiles():
    """Every length from 1 to three chunks and one, cold and then as a
    prefix-cache hit, finds its programs built: no new signature of any
    wrapped entry point and no executable built or loaded by jax."""
    eng = InferenceEngine(EngineConfig(**LADDER, prefix_cache=True))
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
    assert set(model_launches(seen)) == {16, 32}


def test_follower_replays_the_liaisons_widths():
    liaison = InferenceEngine(EngineConfig(**LADDER, prefix_cache=True))
    follower = InferenceEngine(EngineConfig(**LADDER, prefix_cache=True))
    records: list[dict] = []
    liaison.plan_sink = records.append
    led, followed = chunk_widths(liaison), chunk_widths(follower)
    # cold with a short tail, its re-ask, a tail too long for the narrow
    # width, and a prompt that ends on a chunk boundary
    for i, n in enumerate((70, 70, 90, 64)):
        res = liaison.generate(GenerationRequest(
            id=f"q{i}", raw=True, prompt_ids=ids(n, salt=n), options=GREEDY))
        assert res.done_reason in ("stop", "length")
    admits = [r for r in records if r["op"] == "admit"]
    assert [r["cached"] for r in admits] == [0, 64, 0, 0]
    for rec in records:
        follower.apply_plan_op(rec)
    assert followed == led
    assert model_launches(led) == [32, 32, 16, 16, 32, 32, 32, 32, 32]
    np.testing.assert_array_equal(np.asarray(follower.tokens),
                                  np.asarray(liaison.tokens))
    np.testing.assert_array_equal(np.asarray(follower.window),
                                  np.asarray(liaison.window))


def test_a_pp_engine_picks_the_same_widths_on_prefill_chunk():
    """Pipeline engines admit chunk by chunk through prefill_chunk_fn (no
    mixed step): the same helper, the same widths, the one-width tokens."""
    from gridllm_tpu.parallel.mesh import MeshConfig

    out = []
    for narrow in (16, 32):
        eng = InferenceEngine(EngineConfig(**{
            **TINY, "max_slots": 2, "prefill_chunk_narrow": narrow,
            "mesh": MeshConfig(pp=2, dp=2, tp=2)}))
        assert not eng._use_mixed
        seen = chunk_widths(eng)
        res = eng.generate(GenerationRequest(
            id="pp", raw=True, prompt_ids=ids(70, salt=9), options=GREEDY))
        assert res.done_reason in ("stop", "length"), res.error
        out.append((res.token_ids, seen))
    (tok_l, seen_l), (tok_o, seen_o) = out
    assert tok_l == tok_o and len(tok_l) == 6
    assert seen_l == [("prefill_chunk", 32), ("prefill_chunk", 32),
                      ("prefill_chunk", 16)]
    assert seen_o == [("prefill_chunk", 32)] * 3


def test_counters_say_how_often_the_narrow_width_engages():
    eng = InferenceEngine(EngineConfig(**LADDER, prefix_cache=True))
    name = eng.cfg.name

    def read():
        return ({w: _CHUNK_LAUNCHES.value(model=name, width=w)
                 for w in ("16", "32")},
                {k: _CHUNK_TOKENS.value(model=name, kind=k)
                 for k in ("real", "padded")})
    l0, t0 = read()
    prompt = ids(70, salt=3)
    for i in range(2):       # cold: 32 + 32 + 6 in 16; the re-ask: 6 in 16
        eng.generate(GenerationRequest(id=f"c{i}", raw=True,
                                       prompt_ids=prompt, options=GREEDY))
    eng.generate(GenerationRequest(id="short", raw=True, prompt_ids=ids(9),
                                   options=GREEDY))    # one bucket: no chunk
    l1, t1 = read()
    assert {w: l1[w] - l0[w] for w in l1} == {"16": 2, "32": 2}
    assert t1["real"] - t0["real"] == 70 + 6
    assert t1["padded"] - t0["padded"] == 32 + 32 + 16 + 16
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
    # the same length as text runs its tail at the narrow width
    del seen[:]
    eng.generate(GenerationRequest(
        id="txt", raw=True, prompt_ids=ids(19), options=opts))
    assert model_launches(seen) == [16, 8]
