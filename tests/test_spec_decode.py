"""Speculative decoding tests (ISSUE 5): greedy spec-on vs spec-off
token-stream parity, mid-span stop-sequence truncation, KV
rollback-to-length units (page-boundary crossing + ref-counted cached
pages), n-gram drafter units, and zero steady-state recompiles with
speculation armed (reusing the PR-4 tripwire harness)."""

import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.obs.perf import recompile_totals
from gridllm_tpu.ops.kvcache import (
    PagedKVCache,
    PageAllocator,
    gather_kv,
    rollback_to_length,
    write_decode_all,
    write_multi_all,
)
from gridllm_tpu.ops.spec import NgramDrafter, make_drafter

TINY = dict(
    model="tiny-llama",
    max_slots=4,
    page_size=8,
    num_pages=64,
    max_pages_per_slot=8,
    prefill_buckets=(16, 32),
)

# repetitive prompt + penalty off: greedy output settles into a cycle the
# n-gram drafter can extend, so parity tests exercise REAL acceptance
REP_PROMPT = "ab ab ab ab ab ab"
REP_OPTS = {"temperature": 0.0, "repeat_penalty": 1.0, "num_predict": 24}


@pytest.fixture(scope="module")
def spec_on():
    return InferenceEngine(EngineConfig(**TINY, spec_decode=True, spec_k=4))


@pytest.fixture(scope="module")
def spec_off():
    return InferenceEngine(EngineConfig(**TINY, spec_decode=False))


# ---------------------------------------------------------------------------
# drafter units
# ---------------------------------------------------------------------------


def test_drafter_matches_most_recent_occurrence():
    d = NgramDrafter(max_n=3, min_n=1)
    #        0  1  2  3  4  5  6  7
    ids = [1, 2, 3, 9, 1, 2, 3, 5, 1, 2, 3]
    # suffix [1,2,3] matched at its MOST RECENT earlier occurrence (idx 4)
    assert d.draft(ids, 4) == [5, 1, 2, 3]


def test_drafter_prefers_longest_suffix():
    d = NgramDrafter(max_n=3, min_n=1)
    # last-2 [7, 8] occurs earlier (→ 9); last-1 [8] also occurs (→ 1);
    # the longer match wins
    ids = [7, 8, 9, 8, 1, 7, 8]
    assert d.draft(ids, 2) == [9, 8]


def test_drafter_no_match_and_bounds():
    d = NgramDrafter(max_n=3, min_n=1)
    assert d.draft([1, 2, 3, 4], 4) == []      # no recurring suffix
    assert d.draft([5], 4) == []               # too short
    assert d.draft([1, 2, 1, 2], 0) == []      # k = 0
    assert d.draft([1, 2, 1], 2) == [2, 1]     # continuation truncated at end


def test_drafter_lookback_bounds_scan():
    far = [1, 2, 3] + [9] * 50 + [1, 2]
    assert NgramDrafter(max_n=2, min_n=2).draft(far, 1) == [3]
    assert NgramDrafter(max_n=2, min_n=2, lookback=10).draft(far, 1) == []


def test_drafter_factory_env(monkeypatch):
    monkeypatch.setenv("GRIDLLM_SPEC_NGRAM_MAX", "7")
    d = make_drafter()
    assert isinstance(d, NgramDrafter) and d.max_n == 7
    with pytest.raises(ValueError):
        make_drafter("nope")


def _walk_reference(ids, k, max_n, min_n, lookback):
    """The backward scan NgramDrafter.draft ran until PR 37, verbatim (self.x
    read as arguments): the oracle the compiled lookup is held to."""
    ids = list(ids)
    n_ids = len(ids)
    if k <= 0 or n_ids < min_n + 1:
        return []
    lo = 0 if not lookback else max(n_ids - lookback, 0)
    for n in range(min(max_n, n_ids - 1), min_n - 1, -1):
        suffix = ids[n_ids - n:]
        # most recent occurrence strictly before the suffix itself
        for i in range(n_ids - n - 1, lo - 1, -1):
            if ids[i : i + n] == suffix:
                cont = ids[i + n : i + n + k]
                if cont:
                    return cont
                break  # suffix only recurs at the very end — shorter n
    return []


@pytest.mark.parametrize("lookback", [0, 10, 1000])
@pytest.mark.parametrize("max_n, min_n", [(4, 1), (2, 2), (7, 1)])
@pytest.mark.parametrize("alphabet", [2, 16, 256, 100_000])
def test_lookup_proposes_what_the_walk_proposed(alphabet, max_n, min_n,
                                                lookback):
    """Token for token, stateless and through a slot's life as the engine
    drives it. The four alphabets make every n from max_n down to 1 both
    hit and miss; a tail copied from earlier history makes long n hit in
    the large ones."""
    rng = np.random.RandomState(alphabet + 31 * max_n + 7 * lookback)
    d = NgramDrafter(max_n=max_n, min_n=min_n, lookback=lookback)
    seen: set[bool] = set()

    def fresh(n):
        return [int(t) for t in rng.randint(0, alphabet, n)]

    def same(ids, k, slot=None):
        want = _walk_reference(ids, k, max_n, min_n, lookback)
        assert d.draft(ids, k, slot) == want, (len(ids), k, slot)
        seen.add(bool(want))

    def grow(ids):
        """One to six more tokens: new ones, or (an accepted draft) the
        ones that followed an earlier position."""
        n = int(rng.randint(1, 7))
        at = int(rng.randint(0, len(ids)))
        ids.extend(ids[at:at + n] if rng.rand() < 0.5 else fresh(n))

    for n_ids in (1, 2, 3, 4, 5, 8, 9, 17, 64, 300, 1500, 5000):
        ids = fresh(n_ids)
        for k in range(9):
            same(ids, k)
        ids += ids[n_ids // 3:n_ids // 3 + 5]       # a tail that recurs
        same(ids, 4)

    # 256 then 0 hold the four bytes of 1 across their boundary: not an
    # occurrence of 1, and not in the way of the one before it
    same([256, 0, 5, 1], 4)
    same([1, 7, 256, 0, 5, 1], 4)

    # the slot's life: admitted with a prompt, 60 verify steps
    ids = fresh(int(rng.randint(1, 2500)))
    for step in range(60):
        same(ids, step % 9, slot=3)
        grow(ids)
    # the same list popped at its tail (EOS), then popped and given
    # another token in that place between two calls
    same(ids, 4, slot=3)
    ids.pop()
    same(ids, 4, slot=3)
    ids.append(ids.pop() ^ 1)
    same(ids, 4, slot=3)
    # finished; a shorter prompt reuses the slot
    d.reset_slot(3)
    ids = fresh(40) * 2
    for _ in range(5):
        same(ids, 8, slot=3)
        grow(ids)
    # a history that is not an extension of the one held, with no reset:
    # shorter, as long (same last token), longer, and empty
    held = len(ids)
    for other in (fresh(7), fresh(held - 1) + ids[-1:], fresh(3 * held), []):
        same(other, 5, slot=3)
        if other:
            grow(other)
        same(other, 5, slot=3)
    # another slot's history is its own
    a, b = fresh(50) * 2, fresh(600)
    for _ in range(4):
        same(a, 3, slot=0)
        same(b, 3, slot=1)
        grow(a), grow(b)
    d.reset()
    same(a, 3, slot=1)
    if alphabet <= 256:
        assert seen == {True, False}


def test_every_draft_the_engine_takes_is_the_walks():
    """In the serving loop: ten requests over four slots (slots reused,
    batches shared, streams ending at their length at different steps),
    every proposal the engine takes is the walk's on that slot's history."""
    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=True, spec_k=4))
    d = eng._drafter
    lookup, calls, wrong = d.draft, [], []

    def checked(ids, k, slot=None):
        got = lookup(ids, k, slot)
        calls.append(slot)
        if got != _walk_reference(ids, k, d.max_n, d.min_n, d.lookback):
            wrong.append((slot, len(ids), got))
        return got

    d.draft = checked
    done = []
    for i in range(10):
        eng.submit(GenerationRequest(
            id=f"w{i}", prompt=("ab " * (2 + i % 4) + f"{i}") * (1 + i % 3),
            options={"temperature": 0.0, "repeat_penalty": 1.0,
                     "num_predict": 4 + 3 * (i % 5)},
            on_chunk=lambda t, fin, res: fin and done.append(res)))
    while eng.step():
        pass
    assert len(done) == 10 and not wrong
    assert len(calls) > 40 and set(calls) <= set(range(4))
    assert not d._held  # every finished slot's history was dropped


# ---------------------------------------------------------------------------
# greedy parity: spec-on streams are byte-identical to spec-off
# ---------------------------------------------------------------------------


def test_greedy_parity_repetitive_with_real_acceptance(spec_on, spec_off):
    r_off = spec_off.generate(
        GenerationRequest(id="p0", prompt=REP_PROMPT, options=dict(REP_OPTS)))
    r_on = spec_on.generate(
        GenerationRequest(id="p1", prompt=REP_PROMPT, options=dict(REP_OPTS)))
    assert r_on.token_ids == r_off.token_ids
    assert r_on.text == r_off.text
    # the parity must not be vacuous: the repetitive stream really
    # speculated and really had drafts accepted
    assert r_on.spec_proposed > 0
    assert r_on.spec_accepted > 0
    assert r_off.spec_proposed == 0  # spec off truly off


def test_greedy_parity_with_repeat_penalty(spec_on, spec_off):
    # default repeat_penalty 1.1: the accept path's in-scan window/counts
    # bookkeeping must track the sequential path's exactly
    opts = {"temperature": 0.0, "num_predict": 16}
    for prompt in ("hello world hello world", "xyzzy", REP_PROMPT):
        r_off = spec_off.generate(
            GenerationRequest(id="q0", prompt=prompt, options=dict(opts)))
        r_on = spec_on.generate(
            GenerationRequest(id="q1", prompt=prompt, options=dict(opts)))
        assert r_on.token_ids == r_off.token_ids, prompt


def test_greedy_parity_concurrent_batch(spec_on, spec_off):
    """Batched spec streams (ragged per-slot accept lengths) still equal
    their solo spec-off outputs."""
    opts = {"temperature": 0.0, "repeat_penalty": 1.0, "num_predict": 10}
    prompts = ("aa aa aa aa", "bc bc bc bc", "hello")
    solo = {
        p: spec_off.generate(
            GenerationRequest(id=p, prompt=p, options=dict(opts))).token_ids
        for p in prompts
    }
    results = {}

    def mk(p):
        def cb(d, done, res):
            if done:
                results[p] = res.token_ids
        return cb

    for p in prompts:
        spec_on.submit(GenerationRequest(
            id=p, prompt=p, options=dict(opts), on_chunk=mk(p)))
    while len(results) < len(prompts):
        spec_on.step()
    assert results == solo


def test_sampled_seeded_deterministic(spec_on):
    """Sampled spec streams are not byte-equal to spec-off (documented:
    the DISTRIBUTION is preserved via rejection sampling) but must stay
    deterministic per (seed, prompt)."""
    opts = {"temperature": 0.9, "seed": 7, "num_predict": 12}
    r1 = spec_on.generate(
        GenerationRequest(id="s1", prompt=REP_PROMPT, options=dict(opts)))
    r2 = spec_on.generate(
        GenerationRequest(id="s2", prompt=REP_PROMPT, options=dict(opts)))
    assert r1.token_ids == r2.token_ids


# ---------------------------------------------------------------------------
# stop sequences / EOS inside an accepted span
# ---------------------------------------------------------------------------


def test_stop_sequence_mid_span_truncates(spec_on, spec_off):
    base = spec_off.generate(GenerationRequest(
        id="b0", prompt=REP_PROMPT, options=dict(REP_OPTS)))
    if len(base.text) < 8:
        pytest.skip("greedy output too short to carve a stop from")
    # a stop buried deep in the stream: by then the spec engine is inside
    # accepted spans, so the stop must truncate MID-span
    stop = base.text[5:8]
    expect = spec_off.generate(GenerationRequest(
        id="b1", prompt=REP_PROMPT,
        options={**REP_OPTS, "stop": [stop]}))
    chunks = []
    got = spec_on.generate(GenerationRequest(
        id="b2", prompt=REP_PROMPT, options={**REP_OPTS, "stop": [stop]},
        on_chunk=lambda d, done, r: chunks.append(d)))
    assert got.text == expect.text
    assert got.token_ids == expect.token_ids
    assert got.done_reason == "stop"
    assert stop not in got.text
    assert "".join(chunks) == got.text  # nothing past the stop ever emitted


def test_num_predict_exact_under_spec(spec_on):
    res = spec_on.generate(GenerationRequest(
        id="np", prompt=REP_PROMPT,
        options={**REP_OPTS, "num_predict": 7}))
    assert res.eval_count == 7
    assert res.done_reason == "length"


# ---------------------------------------------------------------------------
# KV multi-token append + rollback-to-length units
# ---------------------------------------------------------------------------


def _mk_cache(num_pages=8, page_size=4, slots=2, max_pages=4, kvh=2, d=4):
    return PagedKVCache.create(1, num_pages, page_size, kvh, d, slots,
                               max_pages, dtype=jnp.float32)


def _rows(t, kvh=2, d=4, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(1, 1, t, kvh, d), jnp.float32)


def test_write_multi_matches_sequential_decode_writes():
    """write_multi_all(T tokens at once) == T write_decode_all calls."""
    cache_a, cache_b = _mk_cache(), _mk_cache()
    table = jnp.asarray([[0, 1, 2, -1], [3, 4, -1, -1]], jnp.int32)
    active = jnp.asarray([True, True])
    t = 3
    k_new = jnp.concatenate([_rows(t, seed=1), _rows(t, seed=2)], axis=1)
    v_new = jnp.concatenate([_rows(t, seed=3), _rows(t, seed=4)], axis=1)
    base = jnp.asarray([2, 5], jnp.int32)  # slot 1 crosses its page boundary
    positions = base[:, None] + jnp.arange(t)[None]
    ka, va = write_multi_all(cache_a.k, cache_a.v, k_new, v_new, table,
                             positions, active, cache_a.page_size)
    kb, vb = cache_b.k, cache_b.v
    for i in range(t):
        kb, vb = write_decode_all(kb, vb, k_new[:, :, i], v_new[:, :, i],
                                  table, positions[:, i], active,
                                  cache_b.page_size)
    np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb))
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))


def test_write_multi_drops_inactive_and_past_capacity():
    cache = _mk_cache()
    table = jnp.asarray([[0, 1, 2, 3], [4, 5, -1, -1]], jnp.int32)
    t = 4
    k_new = jnp.concatenate([_rows(t, seed=1), _rows(t, seed=2)], axis=1)
    # slot 0 inactive; slot 1 writes 6..9 but owns 2 pages (capacity 8):
    # positions 8, 9 must drop
    positions = jnp.asarray([[0, 1, 2, 3], [6, 7, 8, 9]], jnp.int32)
    k, v = write_multi_all(cache.k, cache.v, k_new, k_new, table, positions,
                           jnp.asarray([False, True]), cache.page_size)
    np.testing.assert_array_equal(np.asarray(k[0, 0]), 0.0)  # inactive slot
    row1, _ = gather_kv(k[0], v[0], table[1], cache.page_size)
    np.testing.assert_array_equal(np.asarray(row1[:6]), 0.0)  # untouched
    assert np.any(np.asarray(row1[6:8]) != 0)                 # written
    # past-capacity positions dropped, page 0 (another slot's!) untouched
    np.testing.assert_array_equal(np.asarray(k[0, 0]), 0.0)


def test_rollback_across_page_boundary_restores_contract():
    """Optimistic K+1 write crossing a page boundary, rollback to the
    accepted length, then the 'true' continuation overwrites the junk —
    the surviving rows must equal a cache that never saw the junk."""
    cache_a, cache_b = _mk_cache(), _mk_cache()
    table = jnp.asarray([[0, 1, 2, -1], [-1, -1, -1, -1]], jnp.int32)
    active = jnp.asarray([True, False])
    ps = cache_a.page_size  # 4
    base = 2  # span 2..6 crosses the page-0 → page-1 boundary
    cache_a = PagedKVCache(k=cache_a.k, v=cache_a.v,
                           page_table=cache_a.page_table,
                           lengths=jnp.asarray([base, 0], jnp.int32),
                           page_size=ps)
    t = 5
    junk_k = jnp.concatenate([_rows(t, seed=10), _rows(t, seed=11)], axis=1)
    positions = cache_a.lengths[:, None] + jnp.arange(t)[None]
    ka, va = write_multi_all(cache_a.k, cache_a.v, junk_k, junk_k, table,
                             positions, active, ps)
    cache_a = PagedKVCache(k=ka, v=va, page_table=cache_a.page_table,
                           lengths=cache_a.lengths, page_size=ps)
    accepted = 2  # keep rows at 2, 3; rows 4..6 are rejected junk
    cache_a = rollback_to_length(
        cache_a, jnp.asarray([base + accepted, 0], jnp.int32))
    assert int(cache_a.lengths[0]) == base + accepted
    # true continuation overwrites the junk region (positions 4..6)
    cont_k = jnp.concatenate([_rows(3, seed=20), _rows(3, seed=21)], axis=1)
    cont_pos = cache_a.lengths[:, None] + jnp.arange(3)[None]
    ka, va = write_multi_all(cache_a.k, cache_a.v, cont_k, cont_k, table,
                             cont_pos, active, ps)
    # reference cache: the accepted rows + continuation, junk never written
    kb, vb = write_multi_all(cache_b.k, cache_b.v, junk_k[:, :, :accepted],
                             junk_k[:, :, :accepted], table,
                             positions[:, :accepted], active, ps)
    kb, vb = write_multi_all(kb, vb, cont_k, cont_k, table, cont_pos,
                             active, ps)
    n_valid = base + accepted + 3
    rows_a, _ = gather_kv(ka[0], va[0], table[0], ps)
    rows_b, _ = gather_kv(kb[0], vb[0], table[0], ps)
    np.testing.assert_array_equal(np.asarray(rows_a[:n_valid]),
                                  np.asarray(rows_b[:n_valid]))


def test_rollback_never_touches_refcount_shared_pages():
    """A warm slot sharing ref-counted prefix-cache pages (PR 3): verify
    writes + rollback live strictly past the prompt, so the shared pages'
    bytes are identical before and after."""
    ps = 4
    alloc = PageAllocator(8, ps, 4, cache_pages=-1)
    prompt = list(range(10))  # 2 full pages (8 tokens) registrable
    alloc.alloc(0, len(prompt) + 2)
    alloc.free(0, prompt)  # registers pages for tokens 0..7
    cached = alloc.match_prefix(1, prompt)
    assert cached == 8
    row = alloc.table_row(1)
    shared = row[:2]
    assert all(alloc._refs[p] == 1 for p in shared)  # pinned by slot 1
    alloc.alloc(1, len(prompt) + 2)

    cache = _mk_cache()
    table = jnp.asarray([row, [-1] * 4], jnp.int32)
    # pretend the shared pages hold real prefix KV
    marker = jnp.ones_like(cache.k[:, 0]) * 7.5
    k = cache.k.at[:, shared[0]].set(marker).at[:, shared[1]].set(marker * 2)
    cache = PagedKVCache(k=k, v=k, page_table=cache.page_table,
                         lengths=jnp.asarray([len(prompt), 0], jnp.int32),
                         page_size=ps)
    before_k = np.asarray(cache.k[:, shared])
    # speculative span at positions >= prompt_len, then rollback
    t = 3
    spec_k = jnp.concatenate([_rows(t, seed=30), _rows(t, seed=31)], axis=1)
    positions = cache.lengths[:, None] + jnp.arange(t)[None]
    ka, va = write_multi_all(cache.k, cache.v, spec_k, spec_k, table,
                             positions, jnp.asarray([True, False]), ps)
    cache = rollback_to_length(
        PagedKVCache(k=ka, v=va, page_table=cache.page_table,
                     lengths=cache.lengths, page_size=ps),
        jnp.asarray([len(prompt) + 1, 0], jnp.int32))
    np.testing.assert_array_equal(np.asarray(cache.k[:, shared]), before_k)


# ---------------------------------------------------------------------------
# recompile tripwire: speculation armed = zero steady recompiles
# ---------------------------------------------------------------------------


def test_zero_steady_recompiles_with_spec_armed(spec_on):
    """Varying batch fill, draft counts, and ragged accept lengths all run
    through ONE compiled verify program — no steady-state recompiles once
    the tripwire is armed (the PR-4 harness contract, now for spec)."""
    assert spec_on.perf.armed  # fixtures above completed requests
    before = recompile_totals()["steady"]
    opts = {"temperature": 0.0, "repeat_penalty": 1.0, "num_predict": 6}
    done = []
    for n in (1, 2, 3):
        for i in range(n):
            spec_on.submit(GenerationRequest(
                id=f"fill{n}-{i}", prompt=REP_PROMPT if i % 2 else "hello",
                options=dict(opts),
                on_chunk=lambda d, fin, res: fin and done.append(res)))
        target = sum((1, 2, 3)[: (1, 2, 3).index(n) + 1])
        while len(done) < target:
            spec_on.step()
    assert recompile_totals()["steady"] == before


def test_spec_stats_flow_to_result_and_state(spec_on):
    res = spec_on.generate(GenerationRequest(
        id="st", prompt=REP_PROMPT, options=dict(REP_OPTS)))
    assert res.spec_proposed >= res.spec_accepted >= 0
    state = spec_on.batch_state()
    assert state["specDecode"]["k"] == 4
    assert state["specDecode"]["steps"] > 0
    assert state["specDecode"]["emitted"] >= state["specDecode"]["accepted"]


def test_spec_env_defaults(monkeypatch):
    """GRIDLLM_SPEC_DECODE defaults on; =0 disables; GRIDLLM_SPEC_K sets
    the depth; EngineConfig overrides env."""
    eng = InferenceEngine(EngineConfig(**TINY))
    assert eng._spec_k == 4  # default-on, default depth
    monkeypatch.setenv("GRIDLLM_SPEC_DECODE", "0")
    assert InferenceEngine(EngineConfig(**TINY))._spec_k == 0
    monkeypatch.setenv("GRIDLLM_SPEC_DECODE", "1")
    monkeypatch.setenv("GRIDLLM_SPEC_K", "2")
    assert InferenceEngine(EngineConfig(**TINY))._spec_k == 2
    assert InferenceEngine(
        EngineConfig(**TINY, spec_decode=False))._spec_k == 0
