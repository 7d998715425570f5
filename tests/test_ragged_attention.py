"""Ragged paged attention: the one kernel/dispatcher that serves chunked
prefill, decode, and spec-verify in one launch.

Four layers of pinning:

- differential: the ragged jnp reference is BIT-identical to the
  per-region references (it delegates to them region-by-region), and the
  interpret-mode kernel matches the reference across mixed batches,
  page-boundary straddles, empty slots, windows, and softcap;
- stream parity: greedy engine token streams under the interpreted
  kernel equal those under the jnp reference — concurrent mixed
  batches, warm prefix-cache replays, and the speculative path included;
- single launch: the kernel-dispatch counters prove an engine compiles
  ONLY `attention_ragged` programs — no other paged-attention label is
  even legal;
- recompile hygiene: varying batch mixes (admissions mid-decode, spec
  verify, warm cache) trigger zero steady-state recompiles.
"""

import os
from contextlib import contextmanager

import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.obs import default_registry
from gridllm_tpu.obs.perf import recompile_totals
from gridllm_tpu.ops import attention as A
from gridllm_tpu.ops import pallas_kernels as PK

TINY = dict(
    model="tiny-llama",
    max_slots=4,
    page_size=8,
    num_pages=64,
    max_pages_per_slot=8,
    prefill_buckets=(16, 32),
    prefill_chunk=16,
)
# long enough to take the chunked (= ragged mixed-step) admission path
LONG_PROMPT = "ab ab ab ab ab ab ab ab ab ab"
GREEDY = {"temperature": 0.0, "repeat_penalty": 1.0, "num_predict": 24}


@contextmanager
def pallas(mode: str):
    """GRIDLLM_PALLAS for the engines built and driven inside: "interpret"
    (the kernel, interpreted) or "0" (the jnp reference). The policy is
    read at trace time, so hold it over generation too."""
    from gridllm_tpu.ops.kvcache import _env_mode

    old = os.environ.get("GRIDLLM_PALLAS")
    os.environ["GRIDLLM_PALLAS"] = mode
    _env_mode.cache_clear()
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("GRIDLLM_PALLAS", None)
        else:
            os.environ["GRIDLLM_PALLAS"] = old
        _env_mode.cache_clear()


def _gen_batch(engine, prompts, opts=GREEDY):
    """Submit all prompts, drive step() until done, return token streams
    in submission order (concurrent batch → mixed steps exercise)."""
    res = {}

    def cb(i):
        def f(_delta, done, r):
            if done:
                res[i] = r

        return f

    for i, p in enumerate(prompts):
        req = GenerationRequest(id=f"r{i}", prompt=p, options=dict(opts))
        req.on_chunk = cb(i)
        engine.submit(req)
    while len(res) < len(prompts):
        engine.step()
    return [res[i] for i in range(len(prompts))]


# ---------------------------------------------------------------------------
# differential: ragged op vs the per-region references / interpret kernel
# ---------------------------------------------------------------------------


def _pools(rng, L=2, P=32, ps=8, kvh=2, d=16):
    kp = jnp.asarray(rng.normal(size=(L, P, ps, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(L, P, ps, kvh, d)), jnp.float32)
    return kp, vp


def test_ragged_ref_bitwise_equals_region_refs():
    """The fallback path delegates region-by-region to the per-region
    references — the same bits as calling each directly."""
    rng = np.random.default_rng(0)
    kp, vp = _pools(rng)
    ps, kvh, d, h = 8, 2, 16, 4
    S, maxp, T = 3, 6, 4
    table = jnp.asarray(
        rng.choice(32, size=S * maxp, replace=False).reshape(S, maxp),
        jnp.int32)
    # lengths straddle page boundaries; slot 1 empty (fresh admission)
    lengths = jnp.asarray([13, 0, 37], jnp.int32)
    li = jnp.int32(1)

    q = jnp.asarray(rng.normal(size=(S, h, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(S, kvh, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(S, kvh, d)), jnp.float32)
    want = A.paged_attention_decode_ref(
        q, kp[1], vp[1], table, lengths, ps, k_cur=kc, v_cur=vc)
    _, got = A.ragged_paged_attention(
        kp, vp, ps, q_group=q[:, None], page_table=table,
        group_lengths=lengths, k_group=kc[:, None], v_group=vc[:, None],
        layer=li, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got[:, 0]))

    qv = jnp.asarray(rng.normal(size=(S, T, h, d)), jnp.float32)
    kcv = jnp.asarray(rng.normal(size=(S, T, kvh, d)), jnp.float32)
    vcv = jnp.asarray(rng.normal(size=(S, T, kvh, d)), jnp.float32)
    wantv = A.paged_attention_verify_ref(
        qv, kp, vp, table, lengths, ps, kcv, vcv, layer=li)
    _, gotv = A.ragged_paged_attention(
        kp, vp, ps, q_group=qv, page_table=table, group_lengths=lengths,
        k_group=kcv, v_group=vcv, layer=li, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(wantv), np.asarray(gotv))

    C = 16
    row, start = table[2], jnp.int32(16)
    qc = jnp.asarray(rng.normal(size=(1, C, h, d)), jnp.float32)
    kcc = jnp.asarray(rng.normal(size=(C, kvh, d)), jnp.float32)
    vcc = jnp.asarray(rng.normal(size=(C, kvh, d)), jnp.float32)
    wantc = A._prefix_chunk_ref(
        qc, kp, vp, row, start, start + C, ps, k_cur=kcc, v_cur=vcc,
        layer=li)
    gotc, _ = A.ragged_paged_attention(
        kp, vp, ps, q_chunk=qc, chunk_row=row, chunk_start=start,
        chunk_total=start + C, k_chunk=kcc, v_chunk=vcc, layer=li,
        use_pallas=False)
    np.testing.assert_array_equal(np.asarray(wantc), np.asarray(gotc))


@pytest.mark.parametrize("softcap,window", [(0.0, 0), (30.0, 0), (0.0, 9)])
def test_ragged_kernel_mixed_batch_matches_ref(softcap, window):
    """ONE interpret-mode launch over chunk + decode + verify regions
    matches the per-region references — incl. page straddles, an empty
    slot, a partially filled last page, softcap, and sliding window."""
    rng = np.random.default_rng(1)
    kp, vp = _pools(rng)
    ps, kvh, d, h = 8, 2, 16, 4
    S, maxp, T, C = 3, 6, 4, 16
    table = jnp.asarray(
        rng.choice(26, size=S * maxp, replace=False).reshape(S, maxp),
        jnp.int32)
    lengths = jnp.asarray([13, 0, 37], jnp.int32)
    li = jnp.int32(0)
    row = jnp.asarray([26, 27, 28, 29, 30, 31], jnp.int32)
    start = jnp.int32(16)   # page-aligned, mid-prompt chunk
    total = start + jnp.int32(11)  # ragged chunk: only 11 of 16 rows valid

    qv = jnp.asarray(rng.normal(size=(S, T, h, d)), jnp.float32)
    kcv = jnp.asarray(rng.normal(size=(S, T, kvh, d)), jnp.float32)
    vcv = jnp.asarray(rng.normal(size=(S, T, kvh, d)), jnp.float32)
    qc = jnp.asarray(rng.normal(size=(1, C, h, d)), jnp.float32)
    kcc = jnp.asarray(rng.normal(size=(C, kvh, d)), jnp.float32)
    vcc = jnp.asarray(rng.normal(size=(C, kvh, d)), jnp.float32)

    wantv = A.paged_attention_verify_ref(
        qv, kp, vp, table, lengths, ps, kcv, vcv, layer=li,
        logit_softcap=softcap, window=window)
    wantc = A._prefix_chunk_ref(
        qc, kp, vp, row, start, total, ps, k_cur=kcc, v_cur=vcc, layer=li,
        logit_softcap=softcap, window=window)

    gc, gg = PK.ragged_attention(
        kp, vp, ps, q_chunk=qc, chunk_row=row, chunk_start=start,
        chunk_total=total, k_chunk=kcc, v_chunk=vcc,
        q_group=qv, page_table=table, group_lengths=lengths,
        k_group=kcv, v_group=vcv, layer=li, interpret=True,
        softcap=softcap, window=window)
    np.testing.assert_allclose(
        np.asarray(gc), np.asarray(wantc), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(gg), np.asarray(wantv), rtol=2e-5, atol=2e-5)


def test_ragged_kernel_group_only_and_chunk_only():
    """Region-absent variants (pure decode step / pure chunk) run the
    same kernel with the other region compiled out."""
    rng = np.random.default_rng(2)
    kp, vp = _pools(rng)
    ps, kvh, d, h = 8, 2, 16, 4
    S, maxp = 3, 6
    table = jnp.asarray(
        rng.choice(32, size=S * maxp, replace=False).reshape(S, maxp),
        jnp.int32)
    lengths = jnp.asarray([7, 25, 1], jnp.int32)
    li = jnp.int32(1)

    q = jnp.asarray(rng.normal(size=(S, 1, h, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(S, 1, kvh, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(S, 1, kvh, d)), jnp.float32)
    want = A.paged_attention_decode_ref(
        q[:, 0], kp[1], vp[1], table, lengths, ps,
        k_cur=kc[:, 0], v_cur=vc[:, 0])
    _, got = PK.ragged_attention(
        kp, vp, ps, q_group=q, page_table=table, group_lengths=lengths,
        k_group=kc, v_group=vc, layer=li, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got[:, 0]), np.asarray(want), rtol=2e-5, atol=2e-5)

    C = 16
    qc = jnp.asarray(rng.normal(size=(1, C, h, d)), jnp.float32)
    kcc = jnp.asarray(rng.normal(size=(C, kvh, d)), jnp.float32)
    vcc = jnp.asarray(rng.normal(size=(C, kvh, d)), jnp.float32)
    row = table[1]
    start = jnp.int32(8)
    wantc = A._prefix_chunk_ref(
        qc, kp, vp, row, start, start + C, ps, k_cur=kcc, v_cur=vcc,
        layer=li)
    gotc, _ = PK.ragged_attention(
        kp, vp, ps, q_chunk=qc, chunk_row=row, chunk_start=start,
        chunk_total=start + C, k_chunk=kcc, v_chunk=vcc, layer=li,
        interpret=True)
    np.testing.assert_allclose(
        np.asarray(gotc), np.asarray(wantc), rtol=2e-5, atol=2e-5)


def test_ragged_kernel_first_chunk_empty_prefix():
    """start == 0 (a fresh prompt's first chunk): no prefix pages are
    streamed, causal attention over the chunk alone."""
    rng = np.random.default_rng(3)
    kp, vp = _pools(rng)
    ps, kvh, d, h = 8, 2, 16, 4
    C = 16
    row = jnp.asarray([0, 1, 2, 3, 4, 5], jnp.int32)
    qc = jnp.asarray(rng.normal(size=(1, C, h, d)), jnp.float32)
    kcc = jnp.asarray(rng.normal(size=(C, kvh, d)), jnp.float32)
    vcc = jnp.asarray(rng.normal(size=(C, kvh, d)), jnp.float32)
    want = A._prefix_chunk_ref(
        qc, kp, vp, row, jnp.int32(0), jnp.int32(C), ps,
        k_cur=kcc, v_cur=vcc)
    got, _ = PK.ragged_attention(
        kp, vp, ps, q_chunk=qc, chunk_row=row, chunk_start=jnp.int32(0),
        chunk_total=jnp.int32(C), k_chunk=kcc, v_chunk=vcc, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# greedy stream parity: the interpreted kernel vs the jnp reference
# ---------------------------------------------------------------------------


def _engine(**kw):
    return InferenceEngine(EngineConfig(**TINY, **kw))


def _streams(mode: str, rounds, **kw):
    """Results of each round of prompts from a fresh engine under
    GRIDLLM_PALLAS=mode, and the engine."""
    with pallas(mode):
        eng = _engine(**kw)
        return [_gen_batch(eng, prompts) for prompts in rounds], eng


def test_greedy_parity_concurrent_mixed_batch():
    """Long (chunked → mixed-step) and short (bucketed) prompts in one
    concurrent batch: identical greedy streams kernel vs reference."""
    # (bf16 random weights: a prompt whose top two logits tie to 1e-4,
    # as "q" does, flips on kernel-vs-reference rounding — not used here)
    prompts = [LONG_PROMPT, "hello", LONG_PROMPT + " xyz", "hi"]
    kw = dict(spec_decode=False, prefix_cache=False)
    (want,), _ = _streams("0", [prompts], **kw)
    (got,), _ = _streams("interpret", [prompts], **kw)
    got = [r.token_ids for r in got]
    assert got == [r.token_ids for r in want]
    assert all(len(t) == GREEDY["num_predict"] for t in got)


def test_greedy_parity_warm_prefix_cache():
    """Warm (cache-hit) admissions replay through the mixed path
    bit-identically: cold == warm, kernel == reference."""
    rounds = [[LONG_PROMPT]] * 2
    want, _ = _streams("0", rounds, spec_decode=False)
    got, on = _streams("interpret", rounds, spec_decode=False)
    got = [r[0].token_ids for r in got]
    assert got == [r[0].token_ids for r in want]
    assert got[0] == got[1]            # cold == warm
    assert on.alloc.hits > 0           # the warm round really hit


def test_greedy_parity_speculative():
    """Spec-on engines: the verify path (one launch over all slots) keeps
    greedy streams identical kernel vs reference, with real acceptance."""
    prompts = [LONG_PROMPT, "hello"]
    kw = dict(spec_decode=True, spec_k=4, prefix_cache=False)
    (want,), _ = _streams("0", [prompts], **kw)
    (got,), _ = _streams("interpret", [prompts], **kw)
    assert [r.token_ids for r in got] == [r.token_ids for r in want]
    assert sum(r.spec_accepted for r in got) > 0


# ---------------------------------------------------------------------------
# single-launch proof: dispatch counters
# ---------------------------------------------------------------------------


def test_single_attention_dispatch_per_step():
    """An engine serving a mixed workload (chunked admission + decode +
    spec verify + warm cache) compiles attention_ragged programs, and the
    per-phase labels of old are not legal dispatch labels at all — the
    kernel-parity rule holds every record_kernel_path literal to the
    legal set, so nothing can record one."""
    from gridllm_tpu.ops.kernels import dispatch_labels

    c = default_registry().get("gridllm_kernel_dispatch_total")

    def count(op):
        return sum(v for labels, v in c.items() if labels["op"] == op)

    before = count("attention_ragged")
    eng = _engine(spec_decode=True, spec_k=4)
    _gen_batch(eng, [LONG_PROMPT, "hello"])
    _gen_batch(eng, [LONG_PROMPT])  # warm-cache replay
    assert count("attention_ragged") > before
    legal = dispatch_labels()
    assert "attention_ragged" in legal
    for op in ("attention_decode", "attention_prefix_chunk",
               "attention_verify"):
        assert op not in legal, op
        assert count(op) == 0, op


# ---------------------------------------------------------------------------
# recompile hygiene: varying batch mixes, zero steady-state recompiles
# ---------------------------------------------------------------------------


def test_zero_steady_recompiles_over_varying_mixes():
    """After the first completed request arms the tripwire, admissions
    mid-decode (mixed steps), different batch fills, spec verify, and
    warm-cache replays must all reuse compiled programs."""
    eng = _engine(spec_decode=True, spec_k=4)
    # warm every program this test's mixes need: chunked + bucketed
    # admission, decode, verify, warm-cache window seeding
    _gen_batch(eng, [LONG_PROMPT, "hello"])
    _gen_batch(eng, [LONG_PROMPT])
    assert eng.perf.armed
    steady0 = recompile_totals()["steady"]
    _gen_batch(eng, [LONG_PROMPT, "hi", LONG_PROMPT + " xyz"])
    _gen_batch(eng, ["hello", LONG_PROMPT])
    steady = recompile_totals()["steady"]
    assert steady == steady0, recompile_totals()["byFn"]


def test_ragged_pool_unpadded_and_memory_fields():
    """_pool_head_dim: an interpreted engine's pool stays at the model's
    head dim, and /admin/memory's allocator math then reports zero
    lane-pad overhead with kvLayout "ragged". (Where kernels compile, a
    head under 128 is stored at 128 lanes, "ragged-padded":
    tests/test_ops.py forces that layout, tests/test_granite_hybrid.py
    holds why there is no other.)"""
    eng = _engine(spec_decode=False)
    alloc = eng.memory_arrays()["alloc"]
    assert alloc["kvLayout"] == "ragged"
    assert alloc["lanePadOverheadBytes"] == 0
    assert eng.cache.k.shape[-1] == eng.cfg.head_dim_
