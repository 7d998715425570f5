"""Unit tests for the compute primitives (CPU, fp32 where it matters).

SURVEY.md §4: the reference has zero unit tests; the rebuild adds numerics
tests the reference never could (its compute lived in Ollama).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.ops import (
    PagedKVCache,
    RopeScaling,
    SamplingParams,
    apply_rope,
    attention_prefill,
    precompute_rope,
    rms_norm,
    sample_tokens,
)
from gridllm_tpu.ops.attention import ragged_paged_attention
from gridllm_tpu.ops.kvcache import PageAllocator, write_decode, write_prefill
from tests.helpers import ragged_decode as _decode


def ref_attention(q, k, v, causal=True):
    """Dense fp32 oracle, GQA via explicit repeat."""
    t, h, d = q.shape
    kvh = k.shape[1]
    k = np.repeat(k, h // kvh, axis=1)
    v = np.repeat(v, h // kvh, axis=1)
    logits = np.einsum("thd,shd->hts", q, k) / np.sqrt(d)
    if causal:
        mask = np.tril(np.ones((t, t), bool))
        logits = np.where(mask[None], logits, -1e30)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hts,shd->thd", p, v)


class TestLayers:
    def test_rms_norm_matches_formula(self):
        x = np.random.RandomState(0).randn(4, 16).astype(np.float32)
        w = np.random.RandomState(1).rand(16).astype(np.float32)
        got = rms_norm(jnp.asarray(x), jnp.asarray(w), eps=1e-6)
        want = x / np.sqrt((x**2).mean(-1, keepdims=True) + 1e-6) * w
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_rope_rotation_preserves_norm(self):
        inv = precompute_rope(64)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 5, 4, 64).astype(np.float32))
        pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
        y = apply_rope(x, pos, inv)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(y), axis=-1),
            np.linalg.norm(np.asarray(x), axis=-1),
            rtol=1e-4,
        )

    def test_rope_position_zero_is_identity(self):
        inv = precompute_rope(32)
        x = jnp.asarray(np.random.RandomState(0).randn(1, 1, 2, 32).astype(np.float32))
        y = apply_rope(x, jnp.zeros((1, 1), jnp.int32), inv)
        np.testing.assert_allclose(y, x, atol=1e-6)

    def test_rope_relative_property(self):
        # <rope(q,m), rope(k,n)> depends only on m-n
        inv = precompute_rope(64)
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, 1, 1, 64).astype(np.float32))
        k = jnp.asarray(rs.randn(1, 1, 1, 64).astype(np.float32))

        def dot(m, n):
            qm = apply_rope(q, jnp.full((1, 1), m, jnp.int32), inv)
            kn = apply_rope(k, jnp.full((1, 1), n, jnp.int32), inv)
            return float(jnp.sum(qm * kn))

        assert dot(5, 3) == pytest.approx(dot(12, 10), rel=1e-4)

    def test_llama3_scaling_changes_low_freqs_only(self):
        base = precompute_rope(128, theta=500000.0)
        scaled = precompute_rope(128, theta=500000.0, scaling=RopeScaling())
        base, scaled = np.asarray(base), np.asarray(scaled)
        assert np.allclose(base[:8], scaled[:8])  # high-freq band untouched
        assert np.allclose(base[-4:] / scaled[-4:], 8.0, rtol=1e-3)  # low-freq /factor


class TestAttention:
    def test_prefill_matches_dense_oracle(self):
        rs = np.random.RandomState(0)
        t, h, kvh, d = 7, 8, 2, 16
        q = rs.randn(1, t, h, d).astype(np.float32)
        k = rs.randn(1, t, kvh, d).astype(np.float32)
        v = rs.randn(1, t, kvh, d).astype(np.float32)
        got = attention_prefill(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.array([t])
        )
        want = ref_attention(q[0], k[0], v[0])
        np.testing.assert_allclose(np.asarray(got)[0], want, rtol=1e-4, atol=1e-5)

    def test_prefill_padding_ignored(self):
        rs = np.random.RandomState(1)
        t, real = 8, 5
        q = rs.randn(1, t, 4, 8).astype(np.float32)
        k = rs.randn(1, t, 4, 8).astype(np.float32)
        v = rs.randn(1, t, 4, 8).astype(np.float32)
        full = attention_prefill(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.array([real]))
        # zero-out padding kv → same result for the first `real` queries
        k2, v2 = k.copy(), v.copy()
        k2[:, real:] = 99.0
        v2[:, real:] = 99.0
        poisoned = attention_prefill(jnp.asarray(q), jnp.asarray(k2), jnp.asarray(v2), jnp.array([real]))
        np.testing.assert_allclose(
            np.asarray(full)[:, :real], np.asarray(poisoned)[:, :real], rtol=1e-5
        )

    def test_paged_decode_matches_prefill_last_token(self):
        """Prefill T-1 tokens into the cache, decode token T → must equal
        row T-1 of full-prefill attention."""
        rs = np.random.RandomState(2)
        t, h, kvh, d, ps = 10, 4, 2, 16, 4
        k_all = rs.randn(t, kvh, d).astype(np.float32)
        v_all = rs.randn(t, kvh, d).astype(np.float32)
        q_all = rs.randn(t, h, d).astype(np.float32)

        cache = PagedKVCache.create(1, 8, ps, kvh, d, max_slots=2, max_pages_per_slot=4, dtype=jnp.float32)
        alloc = PageAllocator(8, ps, 4)
        alloc.alloc(0, t)
        row = jnp.asarray(alloc.table_row(0), jnp.int32)

        kp, vp = write_prefill(
            cache.k[0], cache.v[0],
            jnp.asarray(k_all), jnp.asarray(v_all),
            row, jnp.int32(0), jnp.int32(t), ps,
        )
        table = cache.page_table.at[0].set(row)
        q_last = jnp.asarray(q_all[t - 1 : t])  # [1, H, D] → use as slot 0
        q_batch = jnp.concatenate([q_last, jnp.zeros_like(q_last)], axis=0)
        # token T's K/V ride in fresh (the pool's copy of it is past the
        # prefix and never read); slot 1 is inactive
        cur = lambda x: jnp.concatenate(
            [jnp.asarray(x[t - 1 : t]), jnp.zeros((1, kvh, d), jnp.float32)])
        out = _decode(
            q_batch, kp, vp, table, jnp.array([t - 1, 0], jnp.int32), ps,
            cur(k_all), cur(v_all),
        )
        want = ref_attention(q_all, k_all, v_all)[t - 1]
        np.testing.assert_allclose(np.asarray(out)[0], want, rtol=1e-4, atol=1e-5)

    def test_write_decode_then_attend(self):
        rs = np.random.RandomState(3)
        kvh, d, ps = 2, 8, 4
        cache = PagedKVCache.create(1, 4, ps, kvh, d, max_slots=1, max_pages_per_slot=2, dtype=jnp.float32)
        alloc = PageAllocator(4, ps, 2)
        ks, vs = [], []
        kp, vp = cache.k[0], cache.v[0]
        table = cache.page_table
        for i in range(6):
            alloc.alloc(0, i + 1)
            table = table.at[0].set(jnp.asarray(alloc.table_row(0), jnp.int32))
            kn = rs.randn(1, kvh, d).astype(np.float32)
            vn = rs.randn(1, kvh, d).astype(np.float32)
            ks.append(kn[0]); vs.append(vn[0])
            kp, vp = write_decode(
                kp, vp, jnp.asarray(kn), jnp.asarray(vn), table,
                jnp.array([i], jnp.int32), jnp.array([True]), ps,
            )
        q = rs.randn(1, 4, d).astype(np.float32)
        out = _decode(jnp.asarray(q), kp, vp, table, jnp.array([5], jnp.int32), ps,
                      jnp.asarray(ks[5])[None], jnp.asarray(vs[5])[None])
        want = ref_attention(
            q, np.stack(ks), np.stack(vs), causal=False
        )  # single query attends all 6
        np.testing.assert_allclose(np.asarray(out)[0], want[0], rtol=1e-4, atol=1e-5)


class TestPageAllocator:
    def test_alloc_grow_free_cycle(self):
        a = PageAllocator(num_pages=4, page_size=8, max_pages_per_slot=3)
        assert a.alloc(0, 8) is not None and a.free_pages == 3
        assert a.alloc(0, 9) is not None and a.free_pages == 2  # grew by one page
        assert a.alloc(1, 17) is None  # needs 3, only 2 free
        a.free(0)
        assert a.free_pages == 4
        assert a.alloc(1, 17) is not None

    def test_per_slot_cap(self):
        a = PageAllocator(num_pages=10, page_size=4, max_pages_per_slot=2)
        assert a.alloc(0, 9) is None  # 3 pages > per-slot cap
        assert a.alloc(0, 8) is not None

    def test_table_row_padded(self):
        a = PageAllocator(num_pages=4, page_size=8, max_pages_per_slot=3)
        a.alloc(0, 10)
        row = a.table_row(0)
        assert len(row) == 3 and row.count(-1) == 1


class TestSampling:
    def _params(self, **kw):
        p = SamplingParams.defaults(2)
        for k, v in kw.items():
            setattr(p, k, jnp.asarray(v))
        return p

    def test_greedy_when_temperature_zero(self):
        logits = jnp.asarray(np.random.RandomState(0).randn(2, 100).astype(np.float32))
        p = self._params(temperature=[0.0, 0.0], repeat_penalty=[1.0, 1.0])
        tok = sample_tokens(logits, p)
        np.testing.assert_array_equal(np.asarray(tok), np.argmax(np.asarray(logits), -1))

    def test_seed_determinism_and_step_variation(self):
        logits = jnp.asarray(np.random.RandomState(1).randn(2, 50).astype(np.float32))
        p1 = self._params(temperature=[1.5, 1.5], seed=[7, 7], step=[0, 0])
        a = sample_tokens(logits, p1)
        b = sample_tokens(logits, p1)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # same (seed, step)
        # same params, advancing step → the rng chain must eventually differ
        many_same = all(
            np.array_equal(
                np.asarray(sample_tokens(logits, self._params(temperature=[1.5, 1.5], seed=[7, 7], step=[s, s]))),
                np.asarray(a),
            )
            for s in range(1, 8)
        )
        assert not many_same  # steps advance the rng chain

    def test_top_k_one_is_greedy(self):
        logits = jnp.asarray(np.random.RandomState(2).randn(2, 64).astype(np.float32))
        p = self._params(temperature=[2.0, 2.0], top_k=[1, 1], repeat_penalty=[1.0, 1.0])
        tok = sample_tokens(logits, p)
        np.testing.assert_array_equal(np.asarray(tok), np.argmax(np.asarray(logits), -1))

    def test_top_p_tiny_is_greedy(self):
        logits = jnp.asarray(np.random.RandomState(3).randn(2, 64).astype(np.float32))
        p = self._params(temperature=[2.0, 2.0], top_p=[1e-6, 1e-6], repeat_penalty=[1.0, 1.0])
        tok = sample_tokens(logits, p)
        np.testing.assert_array_equal(np.asarray(tok), np.argmax(np.asarray(logits), -1))

    def test_repeat_penalty_suppresses_seen_token(self):
        # token 0 hugely preferred but heavily penalized and already seen
        logits = np.full((1, 10), -5.0, np.float32)
        logits[0, 0] = 2.0
        logits[0, 1] = 1.9
        counts = np.zeros((1, 10), np.int32)
        counts[0, 0] = 3
        p = SamplingParams.defaults(1)
        p.temperature = jnp.asarray([0.0])
        p.repeat_penalty = jnp.asarray([50.0])
        tok = sample_tokens(jnp.asarray(logits), p, jnp.asarray(counts))
        assert int(tok[0]) == 1

    def test_sampling_respects_distribution(self):
        # two-token distribution ~[0.88, 0.12] at temp 1 — frequencies should track
        logits = jnp.asarray([[2.0, 0.0] + [-30.0] * 62], jnp.float32)
        sampler = jax.jit(sample_tokens)
        n = 200
        hits = 0
        for s in range(n):
            p = SamplingParams.defaults(1)
            p.temperature = jnp.asarray([1.0])
            p.top_k = jnp.asarray([0])
            p.top_p = jnp.asarray([1.0])
            p.repeat_penalty = jnp.asarray([1.0])
            p.step = jnp.asarray([s])
            hits += int(sampler(logits, p)[0] == 0)
        assert 0.75 * n < hits < 0.99 * n


# ---------------------------------------------------------------------------
# Lane-padded pool (d=64 kernel decode path — VERDICT r04 #5)
# ---------------------------------------------------------------------------

def test_decode_dispatch_on_lane_padded_pool_matches_unpadded_ref():
    """The engine allocates D=128 pages for d=64 models; the dispatch pads
    q/k_cur/v_cur and slices out — results must equal attention over the
    unpadded pool."""
    import numpy as np

    from gridllm_tpu.ops.attention import paged_attention_decode_ref

    S, H, KVH, d, dpool = 3, 8, 4, 64, 128
    P_, ps, MPS = 16, 8, 4
    key = jax.random.PRNGKey(0)
    kp = jax.random.normal(key, (P_, ps, KVH, d), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(1), (P_, ps, KVH, d), jnp.float32)
    pad = [(0, 0)] * 3 + [(0, dpool - d)]
    kp_pad, vp_pad = jnp.pad(kp, pad), jnp.pad(vp, pad)
    pt = jnp.tile(jnp.arange(MPS, dtype=jnp.int32)[None], (S, 1))
    lens = jnp.array([9, 0, 25], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(2), (S, H, d), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(3), (S, KVH, d), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(4), (S, KVH, d), jnp.float32)

    # padded-pool dispatch, jnp path
    got = _decode(q, kp_pad, vp_pad, pt, lens, ps, kc, vc, use_pallas=False)
    want = paged_attention_decode_ref(q, kp, vp, pt, lens, ps, k_cur=kc, v_cur=vc)
    np.testing.assert_allclose(got, want, atol=2e-5)

    # padded-pool dispatch, interpret-kernel path
    import os

    os.environ["GRIDLLM_PALLAS"] = "interpret"
    from gridllm_tpu.ops import kvcache

    kvcache._env_mode.cache_clear()
    try:
        got_k = _decode(q, kp_pad, vp_pad, pt, lens, ps, kc, vc)
    finally:
        os.environ.pop("GRIDLLM_PALLAS", None)
        kvcache._env_mode.cache_clear()
    np.testing.assert_allclose(got_k, want, atol=2e-5)


def test_prefix_chunk_on_lane_padded_pool_matches_unpadded():
    import numpy as np

    T, H, KVH, d, dpool = 8, 8, 4, 64, 128
    P_, ps, MPS = 16, 8, 4
    kp = jax.random.normal(jax.random.PRNGKey(0), (P_, ps, KVH, d), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(1), (P_, ps, KVH, d), jnp.float32)
    pad = [(0, 0)] * 3 + [(0, dpool - d)]
    row = jnp.arange(MPS, dtype=jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(2), (1, T, H, d), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(3), (T, KVH, d), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(4), (T, KVH, d), jnp.float32)
    start, total = jnp.int32(8), jnp.int32(8 + 6)

    chunk = dict(q_chunk=q, chunk_row=row, chunk_start=start,
                 chunk_total=total, k_chunk=kc, v_chunk=vc)
    got, _ = ragged_paged_attention(
        jnp.pad(kp, pad), jnp.pad(vp, pad), ps, **chunk)
    want, _ = ragged_paged_attention(kp, vp, ps, **chunk)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_writes_pad_new_rows_to_pool_lanes():
    import numpy as np

    from gridllm_tpu.ops.kvcache import write_decode_all

    L, P_, ps, KVH, d, dpool = 2, 8, 8, 4, 64, 128
    S = 3
    kp = jnp.zeros((L, P_, ps, KVH, dpool), jnp.float32)
    vp = jnp.zeros((L, P_, ps, KVH, dpool), jnp.float32)
    pt = jnp.tile(jnp.arange(4, dtype=jnp.int32)[None], (S, 1))
    positions = jnp.array([0, 9, 17], jnp.int32)
    active = jnp.array([True, True, True])
    kn = jax.random.normal(jax.random.PRNGKey(5), (L, S, KVH, d), jnp.float32)
    vn = jax.random.normal(jax.random.PRNGKey(6), (L, S, KVH, d), jnp.float32)

    out_k, _ = write_decode_all(kp, vp, kn, vn, pt, positions, active, ps,
                                use_pallas=False)
    # row 0 of slot 0 landed in page 0 offset 0, first d lanes = kn, rest 0
    np.testing.assert_allclose(out_k[:, 0, 0, :, :d], kn[:, 0])
    assert float(jnp.abs(out_k[..., d:]).max()) == 0.0


def test_engine_pool_lane_padding_policy(monkeypatch):
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.ops import kvcache

    # CPU auto: kernels off -> no padding
    monkeypatch.delenv("GRIDLLM_PALLAS", raising=False)
    kvcache._env_mode.cache_clear()
    eng = InferenceEngine(EngineConfig(
        model="tiny-llama", max_slots=2, page_size=8, num_pages=16,
        max_pages_per_slot=4, prefill_buckets=(16,),
    ))
    assert eng.cache.k.shape[-1] == eng.cfg.head_dim_

    # forced padded layout (what real TPU gets): pool at 128 lanes, and
    # generation still works through the pad/slice dispatch
    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    monkeypatch.setenv("GRIDLLM_POOL_PAD", "1")
    kvcache._env_mode.cache_clear()
    try:
        eng2 = InferenceEngine(EngineConfig(
            model="tiny-llama", max_slots=2, page_size=8, num_pages=16,
            max_pages_per_slot=4, prefill_buckets=(16,),
        ))
        assert eng2.cache.k.shape[-1] == 128
        from gridllm_tpu.engine import GenerationRequest

        res = eng2.generate(GenerationRequest(
            id="pad", prompt="ab", options={"temperature": 0.0, "num_predict": 4},
        ))
        assert len(res.token_ids) == 4
    finally:
        kvcache._env_mode.cache_clear()
