"""Engine tests: continuous batching, Ollama option semantics, streaming,
checkpoint round-trip. All on tiny-llama with the byte tokenizer (no
external artifacts; SURVEY.md §4 test plan)."""

import numpy as np
import pytest

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine

TINY = dict(
    model="tiny-llama",
    max_slots=4,
    page_size=8,
    num_pages=64,
    max_pages_per_slot=8,
    prefill_buckets=(16, 32),
)


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(EngineConfig(**TINY))


def test_generate_greedy_deterministic(engine):
    opts = {"temperature": 0.0, "num_predict": 8}
    r1 = engine.generate(GenerationRequest(id="a", prompt="hello", options=opts))
    r2 = engine.generate(GenerationRequest(id="b", prompt="hello", options=opts))
    assert r1.token_ids == r2.token_ids
    assert r1.eval_count == 8
    assert r1.done_reason == "length"
    assert r1.prompt_eval_count == len("hello") + 1  # + BOS
    assert r1.total_duration_ns > 0 and r1.prompt_eval_duration_ns > 0


def test_seeded_sampling_deterministic_unseeded_varies(engine):
    opts = {"temperature": 1.0, "num_predict": 12, "seed": 42}
    r1 = engine.generate(GenerationRequest(id="s1", prompt="xyz", options=opts))
    r2 = engine.generate(GenerationRequest(id="s2", prompt="xyz", options=opts))
    assert r1.token_ids == r2.token_ids
    # unseeded requests must NOT be identical across runs (review finding:
    # seed 0 default would make every request deterministic)
    free = {"temperature": 1.0, "num_predict": 12}
    outs = {
        tuple(engine.generate(
            GenerationRequest(id=f"u{i}", prompt="xyz", options=free)).token_ids)
        for i in range(4)
    }
    assert len(outs) > 1


def test_streaming_chunks_concatenate_to_text(engine):
    chunks = []
    req = GenerationRequest(
        id="st", prompt="abc", options={"temperature": 0, "num_predict": 10},
        on_chunk=lambda d, done, res: chunks.append((d, done)),
    )
    res = engine.generate(req)
    assert "".join(d for d, _ in chunks) == res.text
    assert chunks[-1][1] is True
    assert all(not done for _, done in chunks[:-1])


def test_continuous_batching_matches_solo(engine):
    """N concurrent greedy requests produce exactly their solo outputs."""
    opts = {"temperature": 0.0, "num_predict": 6}
    solo = {
        p: engine.generate(GenerationRequest(id=p, prompt=p, options=opts)).token_ids
        for p in ("aa", "bbbb", "ccccc")
    }
    results = {}

    def mk(p):
        def cb(d, done, res):
            if done:
                results[p] = res.token_ids
        return cb

    for p in solo:
        engine.submit(GenerationRequest(id=p, prompt=p, options=opts, on_chunk=mk(p)))
    while len(results) < len(solo):
        engine.step()
    assert results == solo


def test_stop_sequence_trims_and_holds_back(engine):
    base = engine.generate(
        GenerationRequest(id="q0", prompt="qq", options={"temperature": 0, "num_predict": 12})
    )
    if len(base.text) < 3:
        pytest.skip("greedy output too short to carve a stop token from")
    stop = base.text[2:4]
    chunks = []
    res = engine.generate(GenerationRequest(
        id="q1", prompt="qq",
        options={"temperature": 0, "num_predict": 12, "stop": [stop]},
        on_chunk=lambda d, done, r: chunks.append(d),
    ))
    assert stop not in res.text
    assert res.text == base.text[: base.text.find(stop)]
    assert "".join(chunks) == res.text  # nothing beyond the stop ever emitted
    assert res.done_reason == "stop"


def test_num_predict_negative_runs_to_capacity(engine):
    res = engine.generate(GenerationRequest(
        id="cap", prompt="zz", options={"temperature": 0, "num_predict": -1}
    ))
    # tiny pool: 8 pages × 8 tokens per slot = 64-token ceiling
    assert res.done_reason in ("stop", "length")
    assert res.prompt_eval_count + res.eval_count <= 64


def test_oversized_prompt_truncates_left(engine):
    long_prompt = "x" * 200  # > max_context of 64
    res = engine.generate(GenerationRequest(
        id="big", prompt=long_prompt, options={"temperature": 0, "num_predict": 2}
    ))
    assert res.done_reason == "length"
    assert res.prompt_eval_count < 64


def test_embeddings_shape_and_norm(engine):
    vecs = engine.embed(["hello", "world!"])
    assert len(vecs) == 2
    assert len(vecs[0]) == 64  # hidden_size
    assert abs(np.linalg.norm(vecs[0]) - 1.0) < 1e-3
    assert not np.allclose(vecs[0], vecs[1])


def test_checkpoint_roundtrip(tmp_path):
    from gridllm_tpu.engine.loader import load_checkpoint, save_checkpoint
    from gridllm_tpu.models.configs import get_config
    import jax.numpy as jnp

    eng = InferenceEngine(EngineConfig(**TINY))
    cfg = get_config("tiny-llama")
    save_checkpoint(eng.params, cfg, str(tmp_path))
    loaded = load_checkpoint(cfg, str(tmp_path), dtype=jnp.bfloat16)
    orig = eng.params
    for key in ("embed", "final_norm", "lm_head"):
        np.testing.assert_allclose(
            np.asarray(loaded[key], np.float32),
            np.asarray(orig[key], np.float32), rtol=1e-2, atol=1e-2,
        )
    eng2 = InferenceEngine(EngineConfig(**{**TINY, "checkpoint_path": str(tmp_path)}))
    opts = {"temperature": 0.0, "num_predict": 6}
    a = eng.generate(GenerationRequest(id="a", prompt="hi", options=opts))
    b = eng2.generate(GenerationRequest(id="b", prompt="hi", options=opts))
    assert a.token_ids == b.token_ids


def test_chunked_prefill_matches_single_shot():
    """VERDICT.md #4: prompts longer than prefill_chunk run as repeated
    fixed-shape chunk programs against the cached prefix. Greedy output must
    match the single-shot bucket path, and admitting a second long prompt of
    a DIFFERENT length must compile nothing new."""
    chunked = InferenceEngine(EngineConfig(**TINY, prefill_chunk=16))
    single = InferenceEngine(EngineConfig(**TINY, prefill_chunk=64))
    opts = {"temperature": 0.0, "num_predict": 6}

    # the chunk program: the mixed step where the family has one, the
    # per-chunk prefill otherwise (pipeline)
    chunk_fn = (chunked._mixed_chunk_fn if chunked._use_mixed
                else chunked._prefill_chunk_fn)

    prompt = "abcdefgh" * 4  # 33 ids with BOS > chunk 16 → 3 chunks
    r_c = chunked.generate(GenerationRequest(id="c", prompt=prompt, options=opts))
    r_s = single.generate(GenerationRequest(id="s", prompt=prompt, options=opts))
    assert r_c.token_ids == r_s.token_ids
    assert chunk_fn._cache_size() == 1

    # different long length → same compiled program, no new trace
    prompt2 = "zyxwvuts" * 5  # 41 ids
    r2_c = chunked.generate(GenerationRequest(id="c2", prompt=prompt2, options=opts))
    r2_s = single.generate(GenerationRequest(id="s2", prompt=prompt2, options=opts))
    assert r2_c.token_ids == r2_s.token_ids
    assert chunk_fn._cache_size() == 1


def test_embed_batched_matches_single():
    """Batched embeddings (BASELINE config #5) must equal one-at-a-time
    results for every text, across length buckets within one call."""
    eng = InferenceEngine(EngineConfig(**TINY))
    texts = ["a", "hello world", "x" * 30, "medium length text", "b" * 12]
    batched = eng.embed(texts)
    singles = [eng.embed([t])[0] for t in texts]
    for got, want in zip(batched, singles):
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    # unit-norm (bf16 forward → loose tolerance)
    for v in batched:
        assert abs(float(np.linalg.norm(v)) - 1.0) < 5e-2


def test_abort_all_preserves_streamed_text():
    """A failing engine must not rewrite already-streamed text: the final
    result's text stays the concatenation of emitted deltas, and the
    failure message rides res.error (round-1 advisor finding)."""
    eng = InferenceEngine(EngineConfig(**TINY))
    seen: list[tuple[str, bool, object]] = []
    req = GenerationRequest(
        id="x", prompt="hello", options={"temperature": 0.0, "num_predict": 8},
        on_chunk=lambda d, done, res: seen.append((d, done, res)),
    )
    eng.submit(req)
    for _ in range(3):  # admit + a couple of decode steps
        eng.step()
    n = eng.abort_all("boom")
    assert n == 1
    final = seen[-1][2]
    assert final.done_reason == "error"
    assert final.error == "boom"
    streamed = "".join(d for d, _, _ in seen)
    assert streamed == final.text


def test_reset_device_state_recovers():
    """reset_device_state rebuilds donated/poisoned device buffers; the
    engine serves correctly afterwards."""
    eng = InferenceEngine(EngineConfig(**TINY))
    opts = {"temperature": 0.0, "num_predict": 4}
    before = eng.generate(GenerationRequest(id="a", prompt="hi", options=opts))
    # simulate a poisoned cache (what a mid-jit failure leaves behind)
    eng.cache.k.delete()
    eng.reset_device_state()
    after = eng.generate(GenerationRequest(id="b", prompt="hi", options=opts))
    assert before.token_ids == after.token_ids


def test_runner_streams_between_admissions():
    """VERDICT r03 #2/#3: with the runner active, an in-flight stream keeps
    producing tokens while later requests are admitted (bounded admission —
    running streams must not stall for an arrival burst), and concurrent
    streaming requests all complete with per-request live deltas."""
    import threading
    import time as _time

    eng = InferenceEngine(EngineConfig(**TINY, decode_block=2,
                                       admit_per_block=1))
    eng.start()
    try:
        events: list[tuple[str, float]] = []
        done = threading.Event()
        ndone = [0]

        def mk(name, n_total):
            def cb(d, is_done, res):
                if d:
                    events.append((name, _time.perf_counter()))
                if is_done:
                    ndone[0] += 1
                    if ndone[0] == n_total:
                        done.set()
            return cb

        opts = {"temperature": 0.0, "num_predict": 24}
        eng.submit(GenerationRequest(id="a", prompt="aaaa", options=opts,
                                     on_chunk=mk("a", 3)))
        # let "a" start streaming (its programs compile first: seconds on a
        # cold cache), then add two more mid-flight
        deadline = _time.time() + 60
        while not events and _time.time() < deadline:
            _time.sleep(0.002)
        assert events, "stream 'a' never started"
        eng.submit(GenerationRequest(id="b", prompt="bbbb", options=opts,
                                     on_chunk=mk("b", 3)))
        eng.submit(GenerationRequest(id="c", prompt="cccc", options=opts,
                                     on_chunk=mk("c", 3)))
        assert done.wait(timeout=60), "streams did not complete"
        firsts = {}
        for name, t in events:
            firsts.setdefault(name, t)
        # "a" streamed strictly before b/c joined, and kept streaming after
        a_times = [t for n, t in events if n == "a"]
        assert firsts["a"] < firsts["b"] and firsts["a"] < firsts["c"]
        assert max(a_times) > max(firsts["b"], firsts["c"]), (
            "stream 'a' stalled during the admission burst"
        )
    finally:
        eng.stop()


def test_runner_matches_sync_step_tokens():
    """Block-pipelined runner output must be token-identical to the sync
    step() path (same seeds, same prompts)."""
    opts = {"temperature": 0.8, "num_predict": 10, "seed": 7}
    e1 = InferenceEngine(EngineConfig(**TINY))
    want = e1.generate(GenerationRequest(id="w", prompt="hello", options=opts))
    e2 = InferenceEngine(EngineConfig(**TINY, decode_block=4))
    e2.start()
    try:
        got = e2.generate(GenerationRequest(id="g", prompt="hello", options=opts))
    finally:
        e2.stop()
    assert got.token_ids == want.token_ids


def test_cancel_running_via_runner():
    eng = InferenceEngine(EngineConfig(**TINY, decode_block=2))
    eng.start()
    try:
        import threading
        got = {}
        evt = threading.Event()

        def cb(d, done, res):
            if done:
                got["res"] = res
                evt.set()

        eng.submit(GenerationRequest(
            id="victim", prompt="xy",
            options={"temperature": 0.0, "num_predict": -1}, on_chunk=cb,
        ))
        import time as _time
        _time.sleep(0.05)
        cancelled = eng.cancel("victim")
        assert evt.wait(timeout=30)
        if cancelled:
            assert got["res"].done_reason == "cancel"
        else:  # raced to completion before the cancel landed — legal
            assert got["res"].done_reason in ("stop", "length")
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# sampler fidelity: repeat_last_n window, top_k > 64, num_ctx (VERDICT #10)
# ---------------------------------------------------------------------------

def test_repeat_last_n_window_semantics():
    """Tokens outside the repeat_last_n window must stop being penalized:
    with a tiny window the engine's device counts track only the last N
    context tokens (llama.cpp penalty_last_n), not the whole context."""
    import numpy as np

    eng = InferenceEngine(EngineConfig(**TINY, repeat_window=8))
    eng.generate(GenerationRequest(
        id="w1", prompt="abcabcabc",
        options={"temperature": 0, "num_predict": 6, "repeat_last_n": 4},
    ))
    # after the run the slot is freed, but counts of the freed slot remain;
    # the invariant to check: at most repeat_last_n tokens counted
    total = int(np.asarray(eng.counts).sum())
    assert total <= 4, f"window leak: {total} tokens counted (cap 4)"


def test_repeat_last_n_disabled_and_full_context_differ():
    """repeat_last_n=0 disables the penalty entirely; with a strong
    repeat_penalty the outputs must diverge from the windowed default."""
    base = dict(temperature=0, num_predict=12, repeat_penalty=1.9)
    eng = InferenceEngine(EngineConfig(**TINY))
    off = eng.generate(GenerationRequest(
        id="off", prompt="xyxyxyxy", options={**base, "repeat_last_n": 0}))
    on = eng.generate(GenerationRequest(
        id="on", prompt="xyxyxyxy", options={**base, "repeat_last_n": 64}))
    # penalty off → greedy repetition allowed; on → forced divergence
    assert off.token_ids != on.token_ids


def test_top_k_above_64_not_clamped():
    """TOPK lift (was 64): top_k=100 must behave differently from top_k=1
    and the sampler must accept it without clamping to 64."""
    from gridllm_tpu.ops.sampling import TOPK, SamplingParams, sample_tokens
    import jax
    import jax.numpy as jnp

    assert TOPK >= 128
    v = 512
    logits = jax.random.normal(jax.random.PRNGKey(0), (1, v))
    sp = SamplingParams.defaults(1)
    sp = dataclasses_replace(sp, top_k=jnp.asarray([100], jnp.int32),
                             temperature=jnp.asarray([3.0], jnp.float32),
                             top_p=jnp.asarray([1.0], jnp.float32),
                             repeat_penalty=jnp.asarray([1.0], jnp.float32))
    # with a hot temperature and 100 candidates, 40 seeded draws should
    # produce well over 40 distinct... at least more than top_k=1 would
    seen = set()
    for s in range(40):
        spi = dataclasses_replace(sp, seed=jnp.asarray([s], jnp.int32))
        seen.add(int(sample_tokens(logits, spi)[0]))
    assert len(seen) > 10  # far beyond a 1-token or broken-clamp regime


def dataclasses_replace(sp, **kw):
    import dataclasses
    return dataclasses.replace(sp, **kw)


def test_num_ctx_caps_request_context():
    """options.num_ctx caps the slot's context: prompt truncates from the
    left and generation stops at the cap (VERDICT r03 weak #7)."""
    eng = InferenceEngine(EngineConfig(**TINY))
    res = eng.generate(GenerationRequest(
        id="nc", prompt="x" * 100,
        options={"temperature": 0, "num_predict": -1, "num_ctx": 16},
    ))
    assert res.prompt_eval_count < 16
    assert res.prompt_eval_count + res.eval_count <= 16
    assert res.done_reason in ("stop", "length")


def test_byte_tokenizer_round_trip_and_streaming_increments():
    """The byte tokenizer's contract: text round-trips through ids below
    256, BOS/EOS render as nothing, and an id past EOS (all a
    random-weight run at a real vocabulary ever samples) renders as
    exactly one printable character — so a stream of such ids delivers
    one text increment per token instead of nothing until its end."""
    from gridllm_tpu.engine.tokenizer import ByteTokenizer, DetokState

    tok = ByteTokenizer(128_256)
    text = "héllo, wörld — ok"
    ids = tok.encode(text, add_bos=True)
    assert ids[0] == tok.bos_id and all(0 <= i < 256 for i in ids[1:])
    assert tok.decode(ids) == text
    assert tok.decode(ids + [257]) == text             # EOS is not text

    high = [258, 300, 4242, 128_255]
    out = tok.decode(high)
    assert len(out) == len(high) and out.isprintable() and " " not in out
    assert tok.decode([104, 105] + high[:1] + [33]) == "hi" + out[0] + "!"

    # streaming: a multi-byte char split over two tokens is held back
    # until whole; every id past EOS is one increment of one character
    st, seen, got = DetokState(), [], []
    for i in list("é!".encode()) + high:
        seen.append(i)
        got.append(st.delta(tok, seen))
    assert got == ["", "é", "!"] + list(out)
    assert "".join(got) == tok.decode(seen)
