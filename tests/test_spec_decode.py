"""Speculative decoding tests (ISSUE 5): greedy spec-on vs spec-off
token-stream parity, mid-span stop-sequence truncation, KV
rollback-to-length units (page-boundary crossing + ref-counted cached
pages), n-gram drafter units, and zero steady-state recompiles with
speculation armed (reusing the PR-4 tripwire harness)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.engine import engine as engine_mod
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
from tests.helpers import fetch_waits

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


# ---------------------------------------------------------------------------
# the runner's two schedules (ISSUE 54): in series while first proposals are
# accepted, ahead (draftless launches in flight) while none is
# ---------------------------------------------------------------------------

ROOMY = dict(TINY, num_pages=128, max_pages_per_slot=24)
GREEDY = {"temperature": 0.0}
MODEL = "tiny-llama"


class _Never:
    """A chain drafter that proposes nothing."""

    kind = "ngram"

    def __init__(self):
        self.calls = 0

    def draft(self, ids, k, slot=None):
        self.calls += 1
        return []

    def reset_slot(self, slot):
        pass

    def reset(self):
        pass


class _Knows(_Never):
    """Proposes a known stream's continuation once `after` tokens of it are
    in the history, nothing before."""

    def __init__(self, stream, after):
        super().__init__()
        self.stream, self.after = list(stream), after
        self.ahead_at_first = None

    def draft(self, ids, k, slot=None):
        self.calls += 1
        n = len(ids)
        if n < self.after or list(ids) != self.stream[:n]:
            return []
        if self.ahead_at_first is None:
            self.ahead_at_first = _launches("ahead")
        return self.stream[n:n + k]


def _launches(mode):
    return engine_mod._SPEC_LAUNCHES.value(model=MODEL, mode=mode)


def _lookups():
    return sum(engine_mod._SPEC_LOOKUPS.value(model=MODEL, outcome=o)
               for o in ("hit", "miss"))


def _spec_engine(drafter=None, **kw):
    eng = InferenceEngine(EngineConfig(**{**ROOMY, **kw}, spec_decode=True,
                                       spec_k=4))
    eng._drafter = drafter or _Never()
    fetch_waits(eng, 0.0)
    return eng


def _watch_dispatch(eng):
    """The blocks in flight at each verify dispatch."""
    seen, dispatch = [], eng._dispatch_verify

    def watching(drafts, dlen):
        seen.append((len(eng._inflight), int(dlen.sum())))
        dispatch(drafts, dlen)

    eng._dispatch_verify = watching
    return seen


def _serve(eng, reqs, timeout=120.0):
    """Through the runner thread: {id: (result, [(delta, done), ...])}."""
    got, frames, done = {}, {r.id: [] for r in reqs}, threading.Event()

    def on_chunk(rid, user):
        def cb(delta, fin, res):
            frames[rid].append((delta, fin))
            if user:
                user(delta, fin, res)
            if fin:
                got[rid] = res
                if len(got) == len(reqs):
                    done.set()
        return cb

    eng.start()
    try:
        for r in reqs:
            r.on_chunk = on_chunk(r.id, r.on_chunk)
            eng.submit(r)
        assert done.wait(timeout), sorted(got)
    finally:
        eng.stop()
    return {rid: (got[rid], frames[rid]) for rid in got}


def _req(rid, prompt, n, **opts):
    return GenerationRequest(id=rid, prompt=prompt,
                             options={**GREEDY, "num_predict": n, **opts})


def _warm(eng):
    """Past the window: a stream of more launches than _AHEAD_AFTER with
    nothing proposed leaves the runner running ahead."""
    _serve(eng, [_req("warm", "warm up", engine_mod._AHEAD_AFTER + 8)])
    assert eng._spec_quiet >= engine_mod._AHEAD_AFTER


THREE = (("x0", "hello world hello", 60), ("x1", "ab ab ab ab ab", 47),
         ("x2", "xyzzy", 52))


def test_nothing_proposed_runs_ahead_and_says_what_the_series_says(
        monkeypatch):
    """Greedy streams under the default repeat penalty, three at once and
    ending at different launches: byte for byte the same in series (the
    window never closing), running ahead, and with speculation off; and
    running ahead really dispatched launches behind launches."""
    def run(eng):
        out = _serve(eng, [_req(*r) for r in THREE])
        return {rid: (res.token_ids, res.text, res.done_reason)
                for rid, (res, _f) in out.items()}

    ahead = _spec_engine()
    seen, a0 = _watch_dispatch(ahead), _launches("ahead")
    said = run(ahead)
    assert _launches("ahead") > a0
    assert max(n for n, _d in seen) >= 1 and not any(d for _n, d in seen)
    assert said == run(InferenceEngine(EngineConfig(**ROOMY,
                                                    spec_decode=False)))
    monkeypatch.setattr(engine_mod, "_AHEAD_AFTER", 10 ** 9)
    series = _spec_engine()
    seen, a0 = _watch_dispatch(series), _launches("ahead")
    assert said == run(series)
    assert _launches("ahead") == a0 and not any(n for n, _d in seen)
    assert {rid: len(ids) for rid, (ids, _t, _r) in said.items()} == {
        rid: n for rid, _prompt, n in THREE}


def test_a_right_proposal_brings_the_series_back():
    """The drafter is silent for 45 tokens (the runner goes ahead), then
    proposes what the model will say: the first such proposal is held
    against the next launch's token, the series is back within the
    launches already in flight, and drafts are accepted again."""
    prompt = "hello world hello"
    ref = _spec_engine().generate(_req("ref", prompt, 70))
    n_prompt = ref.prompt_eval_count
    knows = _Knows(ref.context, n_prompt + 45)
    eng = _spec_engine(knows)
    seen, a0, s0 = _watch_dispatch(eng), _launches("ahead"), _launches("serial")
    (res, _frames), = _serve(eng, [_req("k", prompt, 70)]).values()
    assert res.token_ids == ref.token_ids and res.text == ref.text
    assert knows.ahead_at_first is not None and knows.ahead_at_first > a0
    assert _launches("ahead") - knows.ahead_at_first <= eng.config.pipeline_depth
    assert res.spec_accepted > 0 and any(d for _n, d in seen)
    # accepted drafts: fewer launches than tokens
    assert (_launches("ahead") - a0) + (_launches("serial") - s0) < 70
    assert eng._spec_quiet < engine_mod._AHEAD_AFTER


@pytest.fixture(scope="module")
def one_slot_ahead():
    """One slot, already past the window: the next request takes over the
    slot the last one left, behind whatever that one left in flight."""
    eng = _spec_engine(max_slots=1)
    _warm(eng)
    return eng


@pytest.mark.parametrize("end", ["eos", "num_predict", "stop", "cancel"])
def test_a_stream_that_ends_under_a_launch_in_flight(one_slot_ahead, spec_off,
                                                     end, monkeypatch):
    """Each way a stream ends, landing while a launch that still counts
    its slot is in flight: nothing past the end is delivered, the pages
    come back, and the request that takes the slot over says what it says
    alone (it ingests nothing of the old launch)."""
    eng = one_slot_ahead
    assert eng._spec_quiet >= engine_mod._AHEAD_AFTER
    full = spec_off.generate(_req("f", REP_PROMPT, 24))
    after = spec_off.generate(_req("g", "xyzzy", 12))
    opts, cut = {}, 6
    if end == "eos":
        eos = full.token_ids[cut]
        assert eos not in full.token_ids[:cut]
        monkeypatch.setattr(eng.tokenizer, "eos_ids", frozenset({eos}))
    elif end == "num_predict":
        opts = {"num_predict": cut}
    elif end == "stop":
        # a stop first found some tokens in, whole characters only
        stop = next(full.text[i:i + 2] for i in range(4, len(full.text) - 2)
                    if "\ufffd" not in full.text[i:i + 2]
                    and full.text.find(full.text[i:i + 2]) == i)
        opts = {"stop": [stop]}
        stopped = spec_off.generate(_req("h", REP_PROMPT, 24, **opts))
        assert stopped.done_reason == "stop" and stopped.token_ids
    a = _req("a", REP_PROMPT, 24, **opts)
    if end == "cancel":
        seen_a = []

        def cancel_later(delta, fin, res):
            seen_a.append(delta)
            if len(seen_a) == cut:
                eng.cancel("a")
        a.on_chunk = cancel_later
    in_flight, finish = [], eng._finish

    def finishing(slot, st, reason, error=""):
        in_flight.append((st.req.id, len(eng._inflight)))
        finish(slot, st, reason, error)

    monkeypatch.setattr(eng, "_finish", finishing)
    out = _serve(eng, [a, _req("b", "xyzzy", 12)])
    (res_a, frames_a), (res_b, _fb) = out["a"], out["b"]
    assert dict(in_flight)["a"] >= 1
    assert res_a.done_reason == {"eos": "stop", "num_predict": "length",
                                 "stop": "stop", "cancel": "cancel"}[end]
    # one final frame, the last; the frames are the text and no more
    assert [fin for _d, fin in frames_a] == [False] * (len(frames_a) - 1) + [True]
    assert "".join(d for d, _fin in frames_a) == res_a.text
    n = len(res_a.token_ids)
    assert res_a.token_ids == full.token_ids[:n]
    if end in ("eos", "num_predict"):
        assert n == cut
    elif end == "stop":
        assert (res_a.text, res_a.token_ids) == (stopped.text,
                                                 stopped.token_ids)
        assert stop not in res_a.text
    else:
        assert cut <= n < 24 and full.text.startswith(res_a.text)
    if end == "eos":
        n_b = next((i for i, t in enumerate(after.token_ids) if t == eos), 12)
        assert res_b.token_ids == after.token_ids[:n_b]
    else:
        assert res_b.token_ids == after.token_ids and res_b.text == after.text
    assert not eng._slots
    assert eng.alloc.free_pages + eng.alloc.cached_pages == eng.config.num_pages


CHURN = ("hello world hello", "ab ab ab ab ab", "xyzzy", "the quick brown fox",
         "a", "one two three four five six seven")


@pytest.mark.parametrize("slots", [3, 8])
def test_churn_every_stream_ends_once_and_every_page_comes_back(
        slots, spec_off, monkeypatch):
    """Some hundreds of short requests, staggered, through the runner
    running ahead on a few slots: streams end under launches in flight
    while others run on, and requests take slots over behind a stale verify
    launch and their own mixed launch. Every request gets one final frame,
    its last; it says what it says alone; no slot and no page is left
    held."""
    import random
    import time

    monkeypatch.setattr(engine_mod, "_AHEAD_AFTER", 4)
    eng = _spec_engine(max_slots=slots, num_pages=256)
    alone = {p: spec_off.generate(_req("ref", p, 14)).token_ids
             for p in CHURN}
    rng = random.Random(slots)
    asked = [(f"r{i}", rng.choice(CHURN), rng.randint(1, 14))
             for i in range(300)]
    ends_under, joins_behind = [], []
    finish, mixed = eng._finish, eng._dispatch_mixed_chunk

    def finishing(slot, st, reason, error=""):
        ends_under.append(len(eng._inflight) and len(eng._slots) > 1)
        finish(slot, st, reason, error)

    def mixing(*a, **kw):
        joins_behind.append(any(e[3] is not None for e in eng._inflight))
        mixed(*a, **kw)

    monkeypatch.setattr(eng, "_finish", finishing)
    monkeypatch.setattr(eng, "_dispatch_mixed_chunk", mixing)
    frames, lock = {rid: [] for rid, _p, _n in asked}, threading.Lock()

    def on_chunk(rid):
        def cb(delta, fin, res):
            with lock:
                frames[rid].append((fin, res))
        return cb

    a0 = _launches("ahead")
    eng.start()
    try:
        for rid, prompt, n in asked:
            req = _req(rid, prompt, n)
            req.on_chunk = on_chunk(rid)
            eng.submit(req)
            time.sleep(rng.choice((0, 0, 0.001, 0.004, 0.01, 0.03)))
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not all(
                f and f[-1][0] for f in frames.values()):
            time.sleep(0.02)
        time.sleep(0.1)     # a second final frame would come now
    finally:
        eng.stop()
    for rid, prompt, n in asked:
        fins = [fin for fin, _res in frames[rid]]
        assert fins == [False] * (len(fins) - 1) + [True], (rid, fins)
        res = frames[rid][-1][1]
        assert res.done_reason == "length", (rid, res.done_reason, res.error)
        assert res.token_ids == alone[prompt][:n], rid
    assert sum(ends_under) >= 50 and sum(joins_behind) >= 10
    assert _launches("ahead") - a0 >= 20
    assert not eng._slots and not eng._pending
    assert sorted(eng._free_slots) == list(range(slots))
    assert eng.alloc.free_pages + eng.alloc.cached_pages == eng.config.num_pages


def test_a_request_admitted_behind_a_launch_in_flight(spec_off):
    """Its mixed launch queues behind the verify launch on the device, its
    first token is read from that launch's own block, and blocks of both
    kinds are ingested in the order they were dispatched."""
    eng = _spec_engine()
    _warm(eng)
    order, behind, first_from = [], [], []
    ingest_block, ingest_spec = eng._ingest_block, eng._ingest_spec
    mixed = eng._dispatch_mixed_chunk

    def firsts(kind, gen):
        for st in eng._slots.values():
            if st.req.id == "late" and st.joined_gen <= gen and not st.generated:
                first_from.append(kind)

    def block(gen, tok):
        order.append(gen)
        firsts("mixed", gen)
        ingest_block(gen, tok)

    def spec(gen, tok, n_emit, dlen):
        order.append(gen)
        firsts("verify", gen)
        ingest_spec(gen, tok, n_emit, dlen)

    def mixing(*a, **kw):
        behind.append([e[3] is not None for e in eng._inflight])
        mixed(*a, **kw)

    eng._ingest_block, eng._ingest_spec = block, spec
    eng._dispatch_mixed_chunk = mixing
    submitted = []

    def then_submit(delta, fin, res):
        submitted.append(delta)
        if len(submitted) == 10:
            eng.submit(late)

    box, done = {}, threading.Event()
    late = _req("late", "xyzzy", 12)
    late.on_chunk = lambda d, fin, res: fin and (box.update(late=res), done.set())
    early = _req("early", REP_PROMPT, 40)
    early.on_chunk = then_submit
    out = _serve(eng, [early])
    if not done.is_set():       # the runner stopped on `early`'s last frame
        eng.start()
        try:
            assert done.wait(60)
        finally:
            eng.stop()
    assert behind[-1] and behind[-1][0], behind   # a verify launch ahead of it
    assert first_from == ["mixed"]
    assert order == sorted(order) and len(set(order)) == len(order)
    assert box["late"].token_ids == spec_off.generate(
        _req("l", "xyzzy", 12)).token_ids
    assert out["early"][0].token_ids == spec_off.generate(
        _req("e", REP_PROMPT, 40)).token_ids


def test_launches_and_lookups_are_counted_on_both_schedules():
    """gridllm_spec_launches_total's two modes add up to the launches the
    phase clock marked, and the drafter is asked (and counted) on both."""
    from gridllm_tpu.obs.perf import PHASE_SECONDS

    eng = _spec_engine()
    launch, at_first_ahead = eng._launch_verify, []

    def launching(drafts, dlen, mode):
        if mode == "ahead" and not at_first_ahead:
            at_first_ahead.append(_lookups())
        launch(drafts, dlen, mode)

    eng._launch_verify = launching
    marks = PHASE_SECONDS.count(model=MODEL, phase="dispatch_verify")
    s0, a0, l0 = _launches("serial"), _launches("ahead"), _lookups()
    _serve(eng, [_req("c0", "hello world", 50), _req("c1", "xyzzy", 44)])
    serial, ahead = _launches("serial") - s0, _launches("ahead") - a0
    assert serial >= engine_mod._AHEAD_AFTER and ahead > 0
    assert serial + ahead == PHASE_SECONDS.count(
        model=MODEL, phase="dispatch_verify") - marks
    assert l0 < at_first_ahead[0] < _lookups()
    assert eng._drafter.calls == _lookups() - l0


def test_launches_longer_than_an_admission_stay_in_series():
    """Nothing is proposed and the window is long past, but the runner
    waits longer at a launch's fetch than an admission takes it: a launch
    kept in flight would hold up the next request's own, so none is."""
    eng = _spec_engine()
    fetch_waits(eng, 10.0)
    seen, a0 = _watch_dispatch(eng), _launches("ahead")
    (res, _frames), = _serve(eng, [_req("slow", "hello world", 60)]).values()
    assert eng._spec_quiet >= engine_mod._AHEAD_AFTER
    assert 0 < max(eng._admits) < min(w for w, _behind in eng._fetch_waits)
    assert not any(behind for _w, behind in eng._fetch_waits)
    assert _launches("ahead") == a0 and not any(n for n, _d in seen)
    assert res.token_ids == _spec_engine().generate(
        _req("ref", "hello world", 60)).token_ids


def test_launch_time_walked_across_admission_time_enters_stays_and_leaves(
        monkeypatch):
    """The second condition's band. A launch of `launch` seconds is read at
    a fetch in series whole and with the runner's wake-up behind it (2 ms
    here), and less an iteration's host work (4 ms) with another launch in
    flight, against admissions of 10 ms. Either way the runner judges the
    wait the launch leaves ahead, and runs ahead while that is past an
    admission by no more than the host's 4 ms: it enters at launch <= 16
    (the series' reading carries the wake-up), stays ahead up to 18, leaves
    above, and once in series stays there down to 16. At 17, inside the
    band, it stays as it is from either side and never alternates. (Until
    ISSUE 60 the series' whole wait was held against the admission alone:
    the band was 10 to 14.) Twelve launches a step of the walk; the median
    of eight has turned by the sixth."""
    from collections import deque

    walk = ((0.024, "serial"), (0.008, "ahead"), (0.017, "ahead"),
            (0.020, "serial"), (0.017, "serial"), (0.012, "ahead"),
            (0.030, "serial"))
    monkeypatch.setattr(engine_mod, "_AHEAD_AFTER", 2)
    eng = _spec_engine()
    seen, launch, mark = [], eng._launch_verify, eng._mark_ingest

    def step():
        return min(len(seen) // 12, len(walk) - 1)

    def launching(drafts, dlen, mode):
        eng._admits = deque([0.010] * 8, maxlen=8)
        # seven of eight: _step_spec adds this CPU's own reading to them
        eng._host_works = deque([0.004] * 8, maxlen=8)
        seen.append((step(), mode))
        launch(drafts, dlen, mode)

    def marking():
        mark()
        # called with the fetched launch popped: another still in flight
        # is the runner ahead of the device; with none, it was woken
        return walk[step()][0] + (-0.004 if eng._inflight else 0.002)

    eng._launch_verify, eng._mark_ingest = launching, marking
    n = 12 * len(walk) + 4
    (res, _frames), = _serve(eng, [_req("w", "hello world", n)]).values()
    for i, (launch_s, mode) in enumerate(walk):
        late = [m for at, m in seen[12 * i + 7:12 * i + 12] if at == i]
        assert late and set(late) == {mode}, (i, seen[12 * i:12 * i + 12])
        if launch_s == 0.017:       # in the band: as it was, every launch
            assert {m for _at, m in seen[12 * i:12 * i + 12]} == {mode}, i
    assert res.token_ids == _spec_engine().generate(
        _req("ref", "hello world", n)).token_ids


def test_an_iterations_host_work_is_its_ingest_draft_and_launch_alone(
        monkeypatch):
    """What `_step_spec` holds a wait against: `_host_works` gets one
    reading an iteration, what the phase clock closed of ingest, draft and
    the launch call since the iteration before. An admission's host work
    (0.5 s each here), the wait at a fetch (0.1 s a launch) and the
    runner's idle wait between two requests (0.5 s) are other phases, and
    no reading holds any of them."""
    from collections import deque

    eng = _spec_engine()
    _warm(eng)                  # every program compiled
    works = eng._host_works = deque()       # every reading, not the last 8
    admit, spec, calls = eng._dispatch_prefill, eng._step_spec, []

    def admitting(*a, **kw):
        time.sleep(0.5)
        return admit(*a, **kw)

    def stepping(ahead_ok=False):
        calls.append(len(works))
        spec(ahead_ok)

    def waiting(out, wait=jax.block_until_ready):
        time.sleep(0.1)
        return wait(out)

    eng._dispatch_prefill, eng._step_spec = admitting, stepping
    monkeypatch.setattr(engine_mod.jax, "block_until_ready", waiting)
    spent0 = eng._clock.spent("ingest", "draft", "dispatch_verify")
    a0 = len(eng._admits)
    _serve(eng, [_req("h0", "hello world", 12)])
    time.sleep(0.5)             # the runner stopped: nobody's time
    eng.start()                 # and idles until a request comes
    try:
        time.sleep(0.5)
    finally:
        eng.stop()
    _serve(eng, [_req("h1", "xyzzy", 12), _req("h2", "ab ab ab", 9)])
    assert calls == list(range(len(calls))) and len(calls) >= 20
    assert len(works) == len(calls)
    assert max(works) < 0.1, sorted(works)[-3:]
    spent = eng._clock.spent("ingest", "draft", "dispatch_verify")
    # the readings are the clock's: all of it up to the last iteration
    assert sum(works) == pytest.approx(spent - spent0, abs=0.05)
    assert eng._clock.seconds["dispatch_prefill"] >= 0.5 * 3
    assert eng._clock.seconds["fetch"] >= 0.1 * len(calls)
    assert len(eng._admits) - a0 == 3


def test_step_never_leaves_a_launch_in_flight():
    """The synchronous driver stays serial however quiet the drafter."""
    eng = _spec_engine()
    a0, done = _launches("ahead"), []
    eng.submit(GenerationRequest(
        id="s", prompt="hello", options={**GREEDY, "num_predict": 50},
        on_chunk=lambda d, fin, res: fin and done.append(res)))
    while eng.step():
        assert not eng._inflight
    assert len(done) == 1 and done[0].eval_count == 50
    assert eng._spec_quiet >= engine_mod._AHEAD_AFTER
    assert _launches("ahead") == a0
