"""Pallas kernels vs the jnp oracles, in interpret mode on CPU
(SURVEY.md §4: engine numerics get golden coverage; the kernels must be
bit-for-bit-close to the reference implementations they replace)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from gridllm_tpu.ops import attention
from gridllm_tpu.ops.attention import (
    _prefix_chunk_ref,
    attention_prefill_ref,
    paged_attention_decode_ref,
    ragged_paged_attention,
)
from gridllm_tpu.ops.kvcache import PageAllocator, PagedKVCache, write_prefill
from gridllm_tpu.ops.pallas_kernels import flash_prefill, ragged_attention
from tests.helpers import ragged_decode


def _last_row_fresh(k_pool, v_pool, table, lengths, ps, layer=None):
    """(prefix, k_cur, v_cur) for a case whose `lengths` count a current
    token that is already in the pool: that row read back out of the pool,
    and the prefix lengths - 1 — the same context, so the same reference."""
    last = jnp.maximum(lengths - 1, 0)
    page = jnp.take_along_axis(table, (last // ps)[:, None], axis=1)[:, 0]
    kl, vl = (k_pool, v_pool) if k_pool.ndim == 4 else (
        k_pool[layer], v_pool[layer])
    return last, kl[page, last % ps], vl[page, last % ps]


def _ragged_decode(q, k_pool, v_pool, table, lengths, ps, k_cur=None,
                   v_cur=None, layer=None, softcap=0.0, window=0):
    """Decode through the interpreted ragged kernel: a group region with
    Td = 1. The kernel always merges the current token's fresh K/V
    in-register; a case without them passes its last pool row as such."""
    if k_cur is None:
        lengths, k_cur, v_cur = _last_row_fresh(
            k_pool, v_pool, table, lengths, ps, layer)
    _, out = ragged_attention(
        k_pool, v_pool, ps, q_group=q[:, None], page_table=table,
        group_lengths=lengths, k_group=k_cur[:, None], v_group=v_cur[:, None],
        layer=layer, interpret=True, softcap=softcap, window=window)
    return out[:, 0]


def _ragged_chunk(q, k_pool, v_pool, row, start, total, ps, k_cur, v_cur,
                  layer=None, softcap=0.0, window=0):
    """Chunked prefill through the interpreted ragged kernel: a chunk
    region alone."""
    out, _ = ragged_attention(
        k_pool, v_pool, ps, q_chunk=q, chunk_row=row, chunk_start=start,
        chunk_total=total, k_chunk=k_cur, v_chunk=v_cur, layer=layer,
        interpret=True, softcap=softcap, window=window)
    return out


@pytest.mark.parametrize("t,h,kvh,d,lens", [
    (64, 4, 2, 16, [64]),          # full block, GQA
    (128, 4, 4, 32, [100]),        # ragged length, MHA
    (256, 8, 2, 64, [256, 17]),    # batch of 2, very ragged
    (64, 2, 1, 128, [1]),          # single valid token
])
def test_flash_prefill_matches_ref(t, h, kvh, d, lens):
    b = len(lens)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, kvh, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, kvh, d), jnp.float32)
    seq_lens = jnp.asarray(lens, jnp.int32)

    want = attention_prefill_ref(q, k, v, seq_lens)
    got = flash_prefill(q, k, v, seq_lens, interpret=True)
    # padding rows (pos >= len) are unspecified; compare valid region only
    for i, ln in enumerate(lens):
        np.testing.assert_allclose(
            np.asarray(got[i, :ln]), np.asarray(want[i, :ln]),
            rtol=2e-5, atol=2e-5,
        )


def test_flash_prefill_bf16():
    t, h, kvh, d = 128, 4, 2, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (1, t, h, d), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(kk, (1, t, kvh, d), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(kv, (1, t, kvh, d), jnp.float32).astype(jnp.bfloat16)
    seq_lens = jnp.asarray([90], jnp.int32)
    want = attention_prefill_ref(q, k, v, seq_lens)
    got = flash_prefill(q, k, v, seq_lens, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got[0, :90], np.float32), np.asarray(want[0, :90], np.float32),
        rtol=3e-2, atol=3e-2,
    )


def _fill_pool(key, lens, page_size=8, kvh=2, d=16, maxp=8, num_pages=32):
    """Build a pool with len(lens) slots holding random K/V of given lengths."""
    s = len(lens)
    cache = PagedKVCache.create(1, num_pages, page_size, kvh, d, s, maxp,
                                dtype=jnp.float32)
    alloc = PageAllocator(num_pages, page_size, maxp)
    k_pool, v_pool = cache.k[0], cache.v[0]
    table = np.full((s, maxp), -1, np.int32)
    for i, ln in enumerate(lens):
        if ln == 0:
            continue
        alloc.alloc(i, ln)
        row = np.asarray(alloc.table_row(i), np.int32)
        table[i] = row
        key, ka, kb = jax.random.split(key, 3)
        # bucket-pad to a multiple of page_size for write_prefill
        t_pad = -(-ln // page_size) * page_size
        k_new = jax.random.normal(ka, (t_pad, kvh, d), jnp.float32)
        v_new = jax.random.normal(kb, (t_pad, kvh, d), jnp.float32)
        k_pool, v_pool = write_prefill(
            k_pool, v_pool, k_new, v_new, jnp.asarray(row), jnp.int32(0),
            jnp.int32(ln), page_size,
        )
    return k_pool, v_pool, jnp.asarray(table), page_size


@pytest.mark.parametrize("lens,h", [
    ([5], 4),              # single slot, partial page
    ([8, 17, 1, 30], 4),   # ragged multi-slot
    ([0, 12], 2),          # inactive slot present
])
def test_ragged_decode_matches_ref(lens, h):
    kvh, d = 2, 16
    k_pool, v_pool, table, ps = _fill_pool(jax.random.PRNGKey(2), lens)
    s = len(lens)
    q = jax.random.normal(jax.random.PRNGKey(3), (s, h, d), jnp.float32)
    lengths = jnp.asarray(lens, jnp.int32)

    want = paged_attention_decode_ref(q, k_pool, v_pool, table, lengths, ps)
    got = _ragged_decode(q, k_pool, v_pool, table, lengths, ps)
    for i, ln in enumerate(lens):
        if ln == 0:
            continue  # inactive slots are unspecified in both impls
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(want[i]), rtol=2e-5, atol=2e-5,
        )


def test_dispatch_env(monkeypatch):
    """GRIDLLM_PALLAS resolves the documented modes; the per-call
    use_pallas override beats the env policy."""
    attention._env_mode.cache_clear()
    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    assert attention._pallas_mode(None) == (True, True)
    assert attention._pallas_mode(False) == (False, True)
    attention._env_mode.cache_clear()
    monkeypatch.setenv("GRIDLLM_PALLAS", "0")
    assert attention._pallas_mode(None) == (False, False)
    assert attention._pallas_mode(True) == (True, False)
    attention._env_mode.cache_clear()
    monkeypatch.setenv("GRIDLLM_PALLAS", "auto")
    use, interp = attention._pallas_mode(None)
    assert use == (jax.default_backend() == "tpu") and interp is False
    attention._env_mode.cache_clear()


def test_model_end_to_end_with_kernels(monkeypatch):
    """tiny-llama greedy decode via the public dispatch (interpret kernels)
    reproduces the pure-jnp path token-for-token."""
    from gridllm_tpu.models import llama
    from gridllm_tpu.models.configs import get_config

    cfg = get_config("tiny-llama")
    params = llama.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    prompt = [5, 17, 99, 3, 42]

    def greedy(n=4):
        cache = PagedKVCache.create(
            cfg.num_layers, 16, 8, cfg.num_kv_heads, cfg.head_dim_, 2, 8,
            dtype=jnp.float32,
        )
        alloc = PageAllocator(16, 8, 8)
        alloc.alloc(0, 16)
        row = jnp.asarray(alloc.table_row(0), jnp.int32)
        padded = jnp.asarray(prompt + [0] * 3, jnp.int32)
        logits, cache = llama.prefill(
            params, cfg, padded, jnp.int32(len(prompt)), cache, jnp.int32(0), row
        )
        out = [int(jnp.argmax(logits))]
        tok = jnp.zeros((2,), jnp.int32).at[0].set(out[0])
        active = jnp.zeros((2,), bool).at[0].set(True)
        for _ in range(n - 1):
            logits, cache = llama.decode_step(params, cfg, tok, cache, active)
            nxt = int(jnp.argmax(logits[0]))
            out.append(nxt)
            tok = tok.at[0].set(nxt)
        return out

    attention._env_mode.cache_clear()
    monkeypatch.setenv("GRIDLLM_PALLAS", "0")
    want = greedy()
    attention._env_mode.cache_clear()
    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    got = greedy()
    attention._env_mode.cache_clear()
    assert got == want


# ---------------------------------------------------------------------------
# paged KV write kernels vs the scatter oracle (interpret mode)
# ---------------------------------------------------------------------------

def test_paged_write_decode_matches_scatter():
    from gridllm_tpu.ops.pallas_kernels import paged_write_decode
    from gridllm_tpu.ops.kvcache import _safe_page_idx, write_decode_all

    L, s, maxp, ps, kvh, d, num_pages = 3, 4, 4, 8, 2, 16, 16
    key = jax.random.PRNGKey(7)
    kp = jax.random.normal(key, (L, num_pages, ps, kvh, d), jnp.float32)
    vp = kp * 2.0
    kn = jax.random.normal(jax.random.PRNGKey(8), (L, s, kvh, d), jnp.float32)
    vn = kn + 1.0
    table = jnp.asarray([
        [3, 1, -1, -1],   # slot 0: 2 pages mapped
        [5, -1, -1, -1],  # slot 1: 1 page
        [7, 8, 9, 10],    # slot 2: full
        [-1, -1, -1, -1], # slot 3: unmapped
    ], jnp.int32)
    pos = jnp.asarray([9, 3, 31, 0], jnp.int32)
    act = jnp.asarray([True, True, True, False])

    want_k, want_v = write_decode_all(
        kp, vp, kn, vn, table, pos, act, ps, use_pallas=False
    )

    srange = jnp.arange(s, dtype=jnp.int32)
    page_idx = _safe_page_idx(
        lambda p: table[srange, p], pos, act, ps, maxp, num_pages
    )
    got_k, got_v = paged_write_decode(
        kp, vp, kn, vn, page_idx, pos % ps, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


@pytest.mark.parametrize("start,length", [
    (0, 32),    # fresh prefill, full pages
    (0, 19),    # fresh prefill, ragged tail (padding rows land in owned page)
    (16, 32),   # chunk continuation, page-aligned start
    (16, 5),    # continuation, ragged
])
def test_paged_write_chunk_matches_scatter_valid_region(start, length):
    """The kernel writes whole pages (incl. padding tails the scatter path
    drops), so compare only positions < start+length — the contract is that
    padded positions are never read (attention masks by length)."""
    from gridllm_tpu.ops.pallas_kernels import paged_write_chunk
    from gridllm_tpu.ops.kvcache import write_prefill_all

    L, t, ps, kvh, d, num_pages, maxp = 2, 32, 8, 2, 16, 16, 8
    kn = jax.random.normal(jax.random.PRNGKey(3), (L, t, kvh, d), jnp.float32)
    vn = kn * 3.0
    kp = jnp.zeros((L, num_pages, ps, kvh, d), jnp.float32)
    vp = jnp.zeros_like(kp)
    row = jnp.asarray([4, 9, 2, 11, 6, 1, 13, 3], jnp.int32)[:maxp]

    want_k, want_v = write_prefill_all(
        kp, vp, kn, vn, row, jnp.int32(start), jnp.int32(length), ps,
        use_pallas=False,
    )
    got_k, got_v = paged_write_chunk(
        kp, vp, kn, vn, row, jnp.int32(start), jnp.int32(length), ps,
        interpret=True,
    )

    # compare per valid absolute position through the table, every layer
    for i in range(length):
        p_abs = start + i
        page = int(row[p_abs // ps])
        off = p_abs % ps
        np.testing.assert_array_equal(
            np.asarray(got_k[:, page, off]), np.asarray(want_k[:, page, off]),
            err_msg=f"k mismatch at abs pos {p_abs}",
        )
        np.testing.assert_array_equal(
            np.asarray(got_v[:, page, off]), np.asarray(want_v[:, page, off]),
        )
    # pages not in this chunk's span must be untouched
    touched = {int(row[(start + i) // ps]) for i in range(max(length, 1))}
    for page in range(num_pages):
        if page not in touched:
            np.testing.assert_array_equal(
                np.asarray(got_k[:, page]), np.asarray(want_k[:, page]),
                err_msg=f"page {page} modified unexpectedly",
            )


def test_ragged_decode_current_token_merge_matches_overlay():
    """Kernel in-register merge == ref overlay mode == written-pool mode."""
    from gridllm_tpu.ops.kvcache import write_decode_all

    s, maxp, ps, kvh, d, num_pages, h = 3, 4, 8, 2, 16, 16, 4
    kq = jax.random.PRNGKey(11)
    q = jax.random.normal(kq, (s, h, d), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(12), (num_pages, ps, kvh, d), jnp.float32)
    vp = kp * 0.5
    kc = jax.random.normal(jax.random.PRNGKey(13), (s, kvh, d), jnp.float32)
    vc = kc - 0.25
    table = jnp.asarray([[3, 1, -1, -1], [5, 6, -1, -1], [7, -1, -1, -1]], jnp.int32)
    prefix = jnp.asarray([9, 13, 0], jnp.int32)  # slot 2: fresh (empty prefix)
    act = jnp.asarray([True, True, True])

    # oracle: write the current token, then attend with lengths incl. it
    kp_w, vp_w = write_decode_all(
        kp[None], vp[None], kc[None], vc[None], table, prefix, act, ps,
        use_pallas=False,
    )
    want = paged_attention_decode_ref(
        q, kp_w[0], vp_w[0], table, prefix + 1, ps
    )

    got_ref = paged_attention_decode_ref(
        q, kp, vp, table, prefix, ps, k_cur=kc, v_cur=vc
    )
    got_kernel = _ragged_decode(
        q, kp, vp, table, prefix, ps, k_cur=kc, v_cur=vc
    )
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_kernel), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_ragged_decode_layer_indexed_pool():
    """5D pool + layer index reads the right layer (kernel and the
    dispatcher's jnp leg)."""
    L, s, maxp, ps, kvh, d, num_pages, h = 3, 2, 2, 8, 2, 16, 8, 4
    q = jax.random.normal(jax.random.PRNGKey(1), (s, h, d), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(2), (L, num_pages, ps, kvh, d), jnp.float32)
    vp = kp + 1.0
    table = jnp.asarray([[1, 2], [4, -1]], jnp.int32)
    lens = jnp.asarray([12, 6], jnp.int32)
    for li in range(L):
        want = paged_attention_decode_ref(q, kp[li], vp[li], table, lens, ps)
        got = _ragged_decode(q, kp, vp, table, lens, ps, layer=jnp.int32(li))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
        # the dispatcher's jnp leg
        last, kc, vc = _last_row_fresh(kp, vp, table, lens, ps, li)
        got2 = ragged_decode(q, kp, vp, table, last, ps, kc, vc,
                             layer=jnp.int32(li), use_pallas=False)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_prefix_chunk_overlay_matches_written_pool():
    """The chunk region with fresh K/V overlaid == chunk already written."""
    from gridllm_tpu.ops.kvcache import write_prefill_all

    t, ps, kvh, d, num_pages, maxp, h = 16, 8, 2, 16, 16, 8, 4
    start, chunk_len = 8, 10
    q = jax.random.normal(jax.random.PRNGKey(5), (1, t, h, d), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(6), (t, kvh, d), jnp.float32)
    vc = kc * 2.0
    row = jnp.asarray([4, 9, 2, 11, 6, 1, 13, 3], jnp.int32)
    # prefix: positions 0..start-1 already in the pool
    kp = jax.random.normal(jax.random.PRNGKey(9), (num_pages, ps, kvh, d), jnp.float32)
    vp = kp - 0.5
    total = jnp.int32(start + chunk_len)

    kp_w, vp_w = write_prefill_all(
        kp[None], vp[None], kc[None], vc[None], row,
        jnp.int32(start), jnp.int32(chunk_len), ps, use_pallas=False,
    )
    want = _prefix_chunk_ref(
        q, kp_w[0], vp_w[0], row, jnp.int32(start), total, ps,
    )
    got, _ = ragged_paged_attention(
        kp, vp, ps, q_chunk=q, chunk_row=row, chunk_start=jnp.int32(start),
        chunk_total=total, k_chunk=kc, v_chunk=vc, use_pallas=False,
    )
    np.testing.assert_allclose(
        np.asarray(got[:, :chunk_len]), np.asarray(want[:, :chunk_len]),
        rtol=2e-5, atol=2e-5,
    )


# ---------------------------------------------------------------------------
# kernel coverage: streamed flash prefill + d=64 padding (VERDICT #9)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,lens", [(256, [256]), (512, [300, 512])])
def test_flash_prefill_streamed_matches_ref(t, lens):
    from gridllm_tpu.ops.pallas_kernels import flash_prefill_streamed

    h, kvh, d = 4, 2, 32
    b = len(lens)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, kvh, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, kvh, d), jnp.float32)
    seq_lens = jnp.asarray(lens, jnp.int32)
    want = attention_prefill_ref(q, k, v, seq_lens)
    got = flash_prefill_streamed(q, k, v, seq_lens, interpret=True)
    for i, ln in enumerate(lens):
        np.testing.assert_allclose(
            np.asarray(got[i, :ln]), np.asarray(want[i, :ln]),
            rtol=2e-5, atol=2e-5,
        )


def test_attention_prefill_routes_streamed_past_vmem_cap(monkeypatch):
    """Past the VMEM budget the dispatch must pick the streaming kernel,
    not fall back to the quadratic-memory jnp path."""
    from unittest import mock
    from gridllm_tpu.ops import attention, pallas_kernels

    monkeypatch.setattr(attention, "_FLASH_KV_VMEM_CAP", 1024)  # force
    t, h, kvh, d = 256, 4, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(1), (1, t, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (1, t, kvh, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (1, t, kvh, d), jnp.float32)
    lens = jnp.asarray([200], jnp.int32)
    want = attention_prefill_ref(q, k, v, lens)
    with mock.patch.object(
        pallas_kernels, "flash_prefill_streamed",
        wraps=pallas_kernels.flash_prefill_streamed,
    ) as spy:
        monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
        attention._env_mode.cache_clear()
        got = attention.attention_prefill(q, k, v, lens)
        attention._env_mode.cache_clear()
        assert spy.called
    np.testing.assert_allclose(
        np.asarray(got[0, :200]), np.asarray(want[0, :200]),
        rtol=2e-5, atol=2e-5,
    )


def test_attention_prefill_d64_pads_to_lane_tile(monkeypatch):
    """qwen2.5-class head_dim 64: the dispatch zero-pads to the 128-lane
    tile, corrects the softmax scale, and slices back — exact vs ref."""
    from gridllm_tpu.ops import attention

    t, h, kvh, d = 128, 4, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(4), (1, t, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(5), (1, t, kvh, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(6), (1, t, kvh, d), jnp.float32)
    lens = jnp.asarray([100], jnp.int32)
    want = attention_prefill_ref(q, k, v, lens)
    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    attention._env_mode.cache_clear()
    got = attention.attention_prefill(q, k, v, lens)
    attention._env_mode.cache_clear()
    assert got.shape == want.shape  # padding sliced back off
    np.testing.assert_allclose(
        np.asarray(got[0, :100]), np.asarray(want[0, :100]),
        rtol=2e-5, atol=2e-5,
    )


@pytest.mark.parametrize("window,softcap", [
    (8, 0.0),       # window only
    (0, 30.0),      # softcap only
    (24, 50.0),     # both (gemma2 shape)
    (1, 50.0),      # degenerate window: self-attention only
])
def test_flash_prefill_softcap_window_matches_ref(window, softcap):
    t, h, kvh, d = 128, 4, 2, 32
    lens = [128, 70]
    b = len(lens)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, kvh, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, kvh, d), jnp.float32)
    seq_lens = jnp.asarray(lens, jnp.int32)

    want = attention_prefill_ref(
        q, k, v, seq_lens, logit_softcap=softcap, window=window)
    got = flash_prefill(q, k, v, seq_lens, interpret=True,
                        softcap=softcap, window=window)
    from gridllm_tpu.ops.pallas_kernels import flash_prefill_streamed

    got_s = flash_prefill_streamed(q, k, v, seq_lens, interpret=True,
                                   softcap=softcap, window=window)
    for i, ln in enumerate(lens):
        np.testing.assert_allclose(
            np.asarray(got[i, :ln]), np.asarray(want[i, :ln]),
            rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(got_s[i, :ln]), np.asarray(want[i, :ln]),
            rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,softcap,merge", [
    (16, 0.0, False),
    (0, 50.0, True),
    (16, 50.0, True),
    (1, 0.0, True),      # window 1: only the merged current token attends
])
def test_ragged_decode_softcap_window_matches_ref(window, softcap, merge):
    lens = [5, 30, 17]
    kvh, d, h = 2, 16, 4
    k_pool, v_pool, table, ps = _fill_pool(jax.random.PRNGKey(11), lens)
    s = len(lens)
    q = jax.random.normal(jax.random.PRNGKey(12), (s, h, d), jnp.float32)
    lengths = jnp.asarray(lens, jnp.int32)
    kc = vc = None
    if merge:
        kc = jax.random.normal(jax.random.PRNGKey(13), (s, kvh, d), jnp.float32)
        vc = jax.random.normal(jax.random.PRNGKey(14), (s, kvh, d), jnp.float32)

    want = paged_attention_decode_ref(
        q, k_pool, v_pool, table, lengths, ps, k_cur=kc, v_cur=vc,
        logit_softcap=softcap, window=window)
    got = _ragged_decode(q, k_pool, v_pool, table, lengths, ps,
                         k_cur=kc, v_cur=vc,
                         softcap=softcap, window=window)
    for i in range(s):
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(want[i]), rtol=2e-5, atol=2e-5)


def test_gemma2_engine_uses_kernels_in_interpret_mode(monkeypatch):
    """The softcap+window model family must keep the Pallas path: force
    interpret-mode kernels and check gemma2 generation matches the
    jnp-path output token-for-token."""
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.engine.engine import GenerationRequest

    kw = dict(model="tiny-gemma2", max_slots=2, page_size=8, num_pages=32,
              max_pages_per_slot=8, prefill_buckets=(16, 32))
    req = dict(prompt="kernel parity check", options={
        "temperature": 0, "num_predict": 6, "seed": 9})

    monkeypatch.setenv("GRIDLLM_PALLAS", "0")
    plain = InferenceEngine(EngineConfig(**kw)).generate(
        GenerationRequest(id="a", **req))
    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    kernels = InferenceEngine(EngineConfig(**kw)).generate(
        GenerationRequest(id="b", **req))
    assert plain.token_ids == kernels.token_ids


@pytest.mark.parametrize("window", [32, 129, 200])
def test_flash_prefill_window_multiblock(window):
    """t=256 = two 128-wide k blocks: the below-window block-skip bounds
    (kb0 in the resident kernel, the pl.when skip in the streamed one)
    actually fire with kb0 > 0 — a single-block case can't regress them.
    window=129 straddles a block boundary."""
    t, h, kvh, d = 256, 4, 2, 32
    lens = [256, 180]
    b = len(lens)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, kvh, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, kvh, d), jnp.float32)
    seq_lens = jnp.asarray(lens, jnp.int32)

    want = attention_prefill_ref(q, k, v, seq_lens, window=window)
    got = flash_prefill(q, k, v, seq_lens, interpret=True, window=window)
    from gridllm_tpu.ops.pallas_kernels import flash_prefill_streamed

    got_s = flash_prefill_streamed(q, k, v, seq_lens, interpret=True,
                                   window=window)
    for i, ln in enumerate(lens):
        np.testing.assert_allclose(
            np.asarray(got[i, :ln]), np.asarray(want[i, :ln]),
            rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(got_s[i, :ln]), np.asarray(want[i, :ln]),
            rtol=2e-5, atol=2e-5)


def test_ragged_decode_window_skips_pages_multipage():
    """Slot long enough (60 tokens, 8/page) that a 16-token window makes
    p0 > 0 — the below-window pages are skipped entirely and the result
    still matches the full-gather oracle."""
    lens = [60]
    kvh, d, h = 2, 16, 4
    k_pool, v_pool, table, ps = _fill_pool(jax.random.PRNGKey(31), lens)
    q = jax.random.normal(jax.random.PRNGKey(32), (1, h, d), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(33), (1, kvh, d), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(34), (1, kvh, d), jnp.float32)
    lengths = jnp.asarray(lens, jnp.int32)
    for window in (16, 17, 8, 3):
        want = paged_attention_decode_ref(
            q, k_pool, v_pool, table, lengths, ps, k_cur=kc, v_cur=vc,
            window=window)
        got = _ragged_decode(q, k_pool, v_pool, table, lengths, ps,
                             k_cur=kc, v_cur=vc, window=window)
        np.testing.assert_allclose(
            np.asarray(got[0]), np.asarray(want[0]), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the ragged kernel's chunk region (chunked prefill against the paged prefix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("start,chunk_valid", [
    (0, 16),       # first chunk, full
    (16, 10),      # second chunk, ragged tail
    (32, 1),       # deep prefix, single valid row
])
def test_prefix_chunk_kernel_matches_jnp(start, chunk_valid):
    """The ragged kernel's chunk region (interpret) == the jnp
    prefix-chunk reference, over a multi-page prefix + in-register chunk
    overlay."""
    t, ps, kvh, d, num_pages, maxp, h = 16, 8, 2, 16, 16, 8, 4
    q = jax.random.normal(jax.random.PRNGKey(5), (1, t, h, d), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(6), (t, kvh, d), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(7), (t, kvh, d), jnp.float32)
    row = jnp.asarray([4, 9, 2, 11, 6, 1, 13, 3], jnp.int32)
    kp = jax.random.normal(jax.random.PRNGKey(9), (num_pages, ps, kvh, d),
                           jnp.float32)
    vp = kp - 0.5
    total = jnp.int32(start + chunk_valid)

    want = _prefix_chunk_ref(
        q, kp, vp, row, jnp.int32(start), total, ps, k_cur=kc, v_cur=vc,
    )
    got = _ragged_chunk(
        q, kp, vp, row, jnp.int32(start), total, ps, k_cur=kc, v_cur=vc,
    )
    np.testing.assert_allclose(
        np.asarray(got[:, :chunk_valid]), np.asarray(want[:, :chunk_valid]),
        rtol=2e-5, atol=2e-5,
    )


def test_prefix_chunk_kernel_full_pool_layer_select():
    """5D pool + traced layer index, matching the in-scan usage."""
    L, t, ps, kvh, d, num_pages, maxp, h = 3, 16, 8, 2, 16, 16, 8, 4
    q = jax.random.normal(jax.random.PRNGKey(1), (1, t, h, d), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(2), (t, kvh, d), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(3), (t, kvh, d), jnp.float32)
    row = jnp.arange(maxp, dtype=jnp.int32)
    kp = jax.random.normal(jax.random.PRNGKey(4), (L, num_pages, ps, kvh, d),
                           jnp.float32)
    vp = kp * 0.7
    start, total = jnp.int32(16), jnp.int32(16 + 16)

    want = _prefix_chunk_ref(
        q, kp, vp, row, start, total, ps, k_cur=kc, v_cur=vc,
        layer=jnp.int32(2),
    )
    got = _ragged_chunk(
        q, kp, vp, row, start, total, ps, k_cur=kc, v_cur=vc,
        layer=jnp.int32(2),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_prefix_chunk_kernel_window_softcap():
    """Sliding window (mistral/gemma2) + softcap through the chunk kernel:
    windows that reach back into the paged prefix must match the jnp
    mask."""
    t, ps, kvh, d, num_pages, maxp, h = 16, 8, 2, 16, 16, 8, 4
    q = jax.random.normal(jax.random.PRNGKey(11), (1, t, h, d), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(12), (t, kvh, d), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(13), (t, kvh, d), jnp.float32)
    row = jnp.arange(maxp, dtype=jnp.int32)
    kp = jax.random.normal(jax.random.PRNGKey(14), (num_pages, ps, kvh, d),
                           jnp.float32)
    vp = kp + 0.3
    start, total = jnp.int32(24), jnp.int32(24 + 16)

    for win in (6, 20):
        want = _prefix_chunk_ref(
            q, kp, vp, row, start, total, ps, k_cur=kc, v_cur=vc,
            logit_softcap=30.0, window=jnp.int32(win),
        )
        got = _ragged_chunk(
            q, kp, vp, row, start, total, ps, k_cur=kc, v_cur=vc,
            softcap=30.0, window=jnp.int32(win),
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_prefix_chunk_dispatch_routes_to_kernel(monkeypatch):
    """The dispatcher's chunk region takes the kernel when interpret
    kernels are on and the chunk fits VMEM; long prompts keep kernel-path
    prefill (VERDICT r04 #5 'done' condition)."""
    from unittest import mock

    from gridllm_tpu.ops import attention, kvcache, pallas_kernels

    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    kvcache._env_mode.cache_clear()
    try:
        t, ps, kvh, d, num_pages, maxp, h = 16, 8, 2, 16, 16, 8, 4
        q = jax.random.normal(jax.random.PRNGKey(0), (1, t, h, d), jnp.float32)
        kc = jax.random.normal(jax.random.PRNGKey(1), (t, kvh, d), jnp.float32)
        vc = jax.random.normal(jax.random.PRNGKey(2), (t, kvh, d), jnp.float32)
        row = jnp.arange(maxp, dtype=jnp.int32)
        kp = jax.random.normal(jax.random.PRNGKey(3), (num_pages, ps, kvh, d),
                               jnp.float32)
        with mock.patch.object(
            pallas_kernels, "ragged_attention",
            wraps=pallas_kernels.ragged_attention,
        ) as spy:
            attention.ragged_paged_attention(
                kp, kp, ps, q_chunk=q, chunk_row=row,
                chunk_start=jnp.int32(8), chunk_total=jnp.int32(8 + 16),
                k_chunk=kc, v_chunk=vc,
            )
            assert spy.called
    finally:
        kvcache._env_mode.cache_clear()
