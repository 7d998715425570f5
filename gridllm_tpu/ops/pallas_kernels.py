"""Pallas TPU attention kernels (SURVEY.md §7 step 5: "paged KV cache +
Pallas flash-attention kernel" is where the baseline metric is won).

Three attention kernels, each with the pure-jnp implementation in
ops/attention.py as its numerical oracle (tests/test_pallas.py runs both
in interpret mode on CPU and asserts equality):

- `flash_prefill`: causal GQA flash attention over one prompt chunk.
  Grid (KVH, q-blocks); K/V for the grid's kv head stay VMEM-resident
  across q blocks; online-softmax accumulation over BK-sized key blocks,
  everything fp32 on the accumulator side, matmuls on the MXU via
  dot_general(preferred_element_type=f32). Causal + length masking via
  broadcasted_iota — no materialized [T, T] mask.

- `flash_prefill_streamed`: the same attention for buckets whose
  per-head K+V exceed the VMEM budget — K/V stream from HBM as a grid
  dimension instead of staying resident.

- `ragged_attention`: every paged phase (chunked prefill, decode,
  spec-verify, tree verify) directly against the HBM page pool, in one
  launch. Grid over query-token tiles; page-table rows and lengths are
  scalar-prefetched (PrefetchScalarGridSpec) so the kernel can DMA
  exactly the valid pages HBM→VMEM, double-buffered to overlap the next
  page's fetch with the current page's math. This is the "stream only
  valid pages" design the jnp oracle's gather materializes densely
  (PAPERS.md "Ragged Paged Attention" — pattern reference only).

Plus two KV-write kernels (`paged_write_decode`, `paged_write_chunk`):
XLA lowers the jnp scatter form of the page-pool update to a serialized
scatter that costs ~12 ms/step (decode) and ~18 ms/prefill for a 3B model
on v5e — measured dominant over the attention math itself (round-4
profiling). These kernels instead DMA exactly the written rows/pages into
the pool in place (input_output_aliases), reducing the write to its true
bandwidth cost (~KB per token per layer).

The reference has no analogue (all compute was Ollama's,
client/src/services/OllamaService.ts); kernel selection lives in
ops/attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# masking sentinel for the online-softmax paths (same value as
# ops/attention.py's _NEG_INF): large enough that exp(x - m) underflows
# to exactly 0 for masked columns, small enough to stay finite in f32 —
# every kernel computes logits in f32, so the value is a deliberate
# dtype commitment (it would overflow f16; dtype-discipline keeps it
# named so the policy is auditable here, once)
_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# flash prefill
# ---------------------------------------------------------------------------

def _flash_prefill_kernel(
    seqlen_ref,  # SMEM (1, 2): [valid tokens, sliding window (0 = full)]
    q_ref,       # VMEM (BQ, 1, G, D) — this q block, this kv head
    k_ref,       # VMEM (1, T, D)     — all keys for this kv head
    v_ref,       # VMEM (1, T, D)
    o_ref,       # VMEM (BQ, 1, G, D)
    *, bq: int, bk: int, t: int, softcap: float,
):
    qi = pl.program_id(1)
    seq_len = seqlen_ref[0, 0]
    window = seqlen_ref[0, 1]
    g, d = q_ref.shape[2], q_ref.shape[3]
    scale = jax.lax.rsqrt(jnp.float32(d))

    q = q_ref[:, 0].reshape(bq * g, d).astype(jnp.float32) * scale
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq * g, bk), 0)
    q_pos = qi * bq + rows // g                       # query position per row
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq * g, bk), 1)

    # key blocks that can contribute to this q block: causal upper bound,
    # tightened by the actual sequence length; with a sliding window the
    # blocks fully BELOW the window are skipped too
    nk = jnp.minimum(
        pl.cdiv((qi + 1) * bq, bk), pl.cdiv(jnp.maximum(seq_len, 1), bk)
    )
    kb0 = jnp.where(
        window > 0, jnp.maximum(qi * bq - window + 1, 0) // bk, 0
    )

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * bk, bk)].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * bk, bk)].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ*G, BK]
        if softcap:  # gemma2: tanh capping BEFORE masking
            logits = softcap * jnp.tanh(logits / softcap)
        k_pos = kb * bk + cols
        dist = q_pos - k_pos
        mask = (dist >= 0) & (k_pos < seq_len) & (
            (window <= 0) | (dist < window)
        )
        logits = jnp.where(mask, logits, _NEG_INF)

        m_new = jnp.maximum(m, logits.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l * alpha + p.sum(axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((bq * g, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq * g, 1), jnp.float32)
    acc0 = jnp.zeros((bq * g, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(kb0, nk, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)
    o_ref[:, 0] = out.reshape(bq, g, d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "softcap"))
def flash_prefill(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    seq_lens: jnp.ndarray,
    interpret: bool = False,
    softcap: float = 0.0,
    window: jnp.ndarray | int = 0,
) -> jnp.ndarray:
    """Causal GQA flash attention. Same contract as
    ops.attention.attention_prefill: q [B, T, H, D], k/v [B, T, KVH, D],
    seq_lens [B] → [B, T, H, D]. T must divide by the q block size
    (min(128, T)); the dispatch layer guarantees this for prefill buckets.
    `softcap` (static): gemma2 tanh logit capping. `window` (scalar, may
    be traced — gemma2 alternates per layer): sliding-window attention,
    0 = full; key blocks fully below a q block's window are skipped.
    """
    b, t, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    bq = min(128, t)
    bk = min(128, t)
    assert t % bq == 0 and t % bk == 0, (t, bq, bk)

    kernel = functools.partial(
        _flash_prefill_kernel, bq=bq, bk=bk, t=t, softcap=softcap
    )
    win = jnp.broadcast_to(jnp.asarray(window, jnp.int32), (b,))

    def one(qb, kb, vb, ln, wn):
        return pl.pallas_call(
            kernel,
            grid=(kvh, t // bq),
            in_specs=[
                pl.BlockSpec((1, 2), lambda kh, i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((bq, 1, g, d), lambda kh, i: (i, kh, 0, 0),
                             memory_space=pltpu.VMEM),
                # kv-head-major layout so the block's last two dims are
                # (T, D) — the TPU lowering requires last-two divisibility
                pl.BlockSpec((1, t, d), lambda kh, i: (kh, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, t, d), lambda kh, i: (kh, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((bq, 1, g, d), lambda kh, i: (i, kh, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((t, kvh, g, d), q.dtype),
            interpret=interpret,
            cost_estimate=pl.CostEstimate(
                flops=4 * t * t * h * d // 2,
                bytes_accessed=(t * h * d + 2 * t * kvh * d) * q.dtype.itemsize,
                transcendentals=t * t * h,
            ),
        )(jnp.stack([ln, wn]).reshape(1, 2), qb.reshape(t, kvh, g, d),
          kb.transpose(1, 0, 2), vb.transpose(1, 0, 2))

    out = jax.vmap(one)(q, k, v, seq_lens.astype(jnp.int32), win)
    return out.reshape(b, t, h, d)


def _flash_prefill_stream_kernel(
    seqlen_ref,  # SMEM (1, 1): valid tokens
    q_ref,       # VMEM (BQ, 1, G, D) — this q block, this kv head
    k_ref,       # VMEM (1, BK, D)    — ONE key block (streamed from HBM)
    v_ref,       # VMEM (1, BK, D)
    o_ref,       # VMEM (BQ, 1, G, D)
    m_scr,       # VMEM (BQ*G, 1) f32 — online-softmax carry across k blocks
    l_scr,       # VMEM (BQ*G, 1) f32
    acc_scr,     # VMEM (BQ*G, D) f32
    *, bq: int, bk: int, softcap: float,
):
    """Streaming variant of _flash_prefill_kernel: the k-block loop is a
    GRID dimension, so K/V blocks are DMA'd HBM→VMEM per step instead of
    pinning [T, D] per head in VMEM — the long-context path past the
    _FLASH_KV_VMEM_CAP budget (VERDICT r03 weak #6 / next-round #9).
    Grid (KVH, q_blocks, k_blocks); the online-softmax state lives in
    scratch, initialized at kb == 0 and finalized into o_ref at the last
    k block."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    seq_len = seqlen_ref[0, 0]
    window = seqlen_ref[0, 1]
    g, d = q_ref.shape[2], q_ref.shape[3]
    scale = jax.lax.rsqrt(jnp.float32(d))

    @pl.when(kb == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: a k block strictly past this q block's last row contributes
    # nothing — skip its math, as do blocks fully below the sliding window
    # (the DMA already happened; index-map-level skipping would revisit
    # blocks and is not worth the complexity here)
    @pl.when(
        (kb * bk <= qi * bq + bq - 1) & (kb * bk < seq_len)
        & ((window <= 0) | ((kb + 1) * bk > qi * bq - window + 1))
    )
    def _():
        q = q_ref[:, 0].reshape(bq * g, d).astype(jnp.float32) * scale
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq * g, bk), 0)
        q_pos = qi * bq + rows // g
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq * g, bk), 1)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if softcap:  # gemma2: tanh capping BEFORE masking
            logits = softcap * jnp.tanh(logits / softcap)
        k_pos = kb * bk + cols
        dist = q_pos - k_pos
        mask = (dist >= 0) & (k_pos < seq_len) & (
            (window <= 0) | (dist < window)
        )
        logits = jnp.where(mask, logits, _NEG_INF)

        m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
        m_new = jnp.maximum(m, logits.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        m_scr[...] = m_new
        l_scr[...] = l * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kb == nk - 1)
    def _():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[:, 0] = out.reshape(bq, g, d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "softcap"))
def flash_prefill_streamed(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    seq_lens: jnp.ndarray,
    interpret: bool = False,
    softcap: float = 0.0,
    window: jnp.ndarray | int = 0,
) -> jnp.ndarray:
    """Same contract as flash_prefill (incl. softcap/window); K/V stream
    from HBM block-by-block (VMEM holds one (BQ q, BK k) tile pair per
    step) — use for prefill buckets whose per-head K+V exceed the VMEM
    budget."""
    b, t, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    bq = min(128, t)
    bk = min(128, t)
    assert t % bq == 0 and t % bk == 0, (t, bq, bk)

    kernel = functools.partial(
        _flash_prefill_stream_kernel, bq=bq, bk=bk, softcap=softcap
    )
    win = jnp.broadcast_to(jnp.asarray(window, jnp.int32), (b,))

    def one(qb, kb_, vb, ln, wn):
        return pl.pallas_call(
            kernel,
            grid=(kvh, t // bq, t // bk),
            in_specs=[
                pl.BlockSpec((1, 2), lambda kh, i, kb: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((bq, 1, g, d), lambda kh, i, kb: (i, kh, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bk, d), lambda kh, i, kb: (kh, kb, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bk, d), lambda kh, i, kb: (kh, kb, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((bq, 1, g, d), lambda kh, i, kb: (i, kh, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((t, kvh, g, d), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((bq * g, 1), jnp.float32),
                pltpu.VMEM((bq * g, 1), jnp.float32),
                pltpu.VMEM((bq * g, d), jnp.float32),
            ],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
        )(jnp.stack([ln, wn]).reshape(1, 2), qb.reshape(t, kvh, g, d),
          kb_.transpose(1, 0, 2), vb.transpose(1, 0, 2))

    out = jax.vmap(one)(q, k, v, seq_lens.astype(jnp.int32), win)
    return out.reshape(b, t, h, d)


# ---------------------------------------------------------------------------
# unified ragged paged attention (ISSUE 6)
# ---------------------------------------------------------------------------


def _ragged_attn_kernel(
    *refs,
    ps: int, bq: int, bk: int, c: int, kvh: int, g: int, d: int,
    td: int, nct: int, softcap: float, has_chunk: bool, has_group: bool,
    quant: bool = False, has_tree: bool = False, dv: int = 0,
):
    """One grid over query-token tiles serving all phases at once
    (the Ragged Paged Attention shape): tiles [0, nct) are the prefill
    chunk's BQ-row blocks (prefix pages streamed HBM→VMEM double-buffered
    + the chunk's own resident K/V, causally masked); tiles
    [nct, nct+S) are one slot each — Td query rows (1 = decode, K+1 =
    spec-verify) against the slot's paged context with the Td fresh K/V
    columns merged in-register (one extra online-softmax step). The DMA
    discipline is shared: every conditional start is guarded by the same
    bound as its wait (scratch + semaphores persist across grid steps).

    `dv` > 0 is a LATENT pool (MLA, absorbed form): one cache head whose
    row is both key (all d values) and value (its first dv): no V pool,
    no V scratch, no fresh-V operands, a page is DMA'd once and read for
    scores and values; the output is dv wide. Its pool, scratch and fresh
    rows come WITHOUT the head axis ([L, P, ps, D], (C, D), (1, Td, D)):
    a head axis of one as the second-minor dimension is padded to the
    sublane tile (twice the bytes in bfloat16) and cannot be sliced."""
    latent = dv > 0
    it = iter(refs)
    scal_ref = next(it)      # SMEM [4]: layer, window, chunk_start, total
    if has_group:
        lens_ref = next(it)      # SMEM [S] per-slot context lengths
        gtable_ref = next(it)    # SMEM [S, maxp]
    if has_tree:
        tpos_ref = next(it)      # SMEM [Td] node depths (tree verify)
        tbits_ref = next(it)     # SMEM [Td] ancestor bitmasks (bit j of
                                 # entry i = node j on node i's root path)
    if has_chunk:
        crow_ref = next(it)      # SMEM [maxp] chunk slot's page row
        qc_ref = next(it)        # VMEM (KVH, BQ*G, D) — rows token-major
        kc_ref = next(it)        # VMEM (C, KVH, D) — resident chunk K
        vc_ref = None if latent else next(it)
    if has_group:
        qg_ref = next(it)        # VMEM (1, KVH, Td*G, D) — rows token-major
        kg_ref = next(it)        # VMEM (1, Td, KVH, D)
        vg_ref = None if latent else next(it)
    k_hbm = next(it)             # ANY [L, P, ps, KVH, D]
    v_hbm = None if latent else next(it)
    if quant:
        ks_hbm = next(it)        # ANY [L, P, ps] f32 per-row scales
        vs_hbm = next(it)
    oc_ref = next(it) if has_chunk else None
    og_ref = next(it) if has_group else None
    k_scr = next(it)             # VMEM (2, ps, KVH, D) double buffer
    v_scr = None if latent else next(it)
    sems = next(it)              # DMA sems (2, 2)
    if quant:
        ks_scr = next(it)        # VMEM (2, ps) f32 scale double buffer
        vs_scr = next(it)
        sc_sems = next(it)       # DMA sems (2, 2)

    i = pl.program_id(0)
    layer = scal_ref[0]
    window = scal_ref[1]
    scale = jax.lax.rsqrt(jnp.float32(d))
    # an interpreted pool keeps the model's head dim (d % 128 != 0: tests
    # stay small) and is lane-padded here, in-register after the load, so
    # every dot runs on 128-lane minors as over the lane-padded pool a
    # compiled kernel is given — numerically exact (zero lanes meet zero
    # q lanes) (`_lp`)
    do = dv if latent else d     # output (value) width
    dop = -(-do // 128) * 128    # ... and the accumulator's, lane-padded

    def _lp(x):
        """Zero-pad a loaded value's last dim to the lane tile."""
        w = x.shape[-1]
        wp = -(-w // 128) * 128
        if wp == w:
            return x
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, wp - w)])

    def _val(x):
        """The value part of a loaded key block: a latent row's first dv
        values; otherwise V is its own operand and this is not called."""
        return x[..., :dv]

    def _head(x, h):
        """Cache head h of a block of rows [N, KVH, D] ([N, D] latent)."""
        return x if latent else x[:, h, :]

    def attend_pages(page_of, ctx_limit, n_table, q_f32, q_abs, q_lo,
                     r, carry):
        """Stream the pages holding keys [0, ctx_limit) (double-buffered)
        into the online-softmax carry. q_f32: [R, KVH, G, D]-ish accessed
        per head as [R, D]; q_abs: [R] absolute query positions (q_lo =
        q_abs minimum, for the window's first-page skip)."""

        def k_dma(slot, page_no):
            page = jnp.maximum(page_of(page_no), 0)
            return pltpu.make_async_copy(
                k_hbm.at[layer, page], k_scr.at[slot], sems.at[slot, 0]
            )

        def v_dma(slot, page_no):
            page = jnp.maximum(page_of(page_no), 0)
            return pltpu.make_async_copy(
                v_hbm.at[layer, page], v_scr.at[slot], sems.at[slot, 1]
            )

        def scale_dmas(slot, page_no):
            # int8 pools (ISSUE 11): the page's [ps] per-row scale rows
            # ride their own small DMAs next to the page copies
            page = jnp.maximum(page_of(page_no), 0)
            return (
                pltpu.make_async_copy(
                    ks_hbm.at[layer, page], ks_scr.at[slot],
                    sc_sems.at[slot, 0]),
                pltpu.make_async_copy(
                    vs_hbm.at[layer, page], vs_scr.at[slot],
                    sc_sems.at[slot, 1]),
            )

        n_pages = jnp.minimum(
            pl.cdiv(jnp.maximum(ctx_limit, 0), ps), n_table
        )
        p0 = jnp.where(window > 0, jnp.maximum(q_lo - window + 1, 0) // ps,
                       0)
        p0 = jnp.minimum(p0, n_pages)

        @pl.when(n_pages > p0)
        def _():
            k_dma(0, p0).start()
            if not latent:
                v_dma(0, p0).start()
            if quant:
                for dma in scale_dmas(0, p0):
                    dma.start()

        def body(p, carry):
            m, l, acc = carry
            slot = jax.lax.rem(p - p0, 2)

            @pl.when(p + 1 < n_pages)
            def _():
                nxt = jax.lax.rem(p + 1 - p0, 2)
                k_dma(nxt, p + 1).start()
                if not latent:
                    v_dma(nxt, p + 1).start()
                if quant:
                    for dma in scale_dmas(nxt, p + 1):
                        dma.start()

            k_dma(slot, p).wait()
            k_page = k_scr[slot]                    # [ps, KVH, D]
            if latent:
                v_page = None
            else:
                v_dma(slot, p).wait()
                v_page = v_scr[slot]
            if quant:
                # dequant epilogue: the flat-row page load multiplies by
                # its [ps, 1] scale column right after the DMA — the dots
                # below see exactly the values an fp pool would hold
                for dma in scale_dmas(slot, p):
                    dma.wait()
                kscale = ks_scr[slot].reshape(ps, 1)
                vscale = vs_scr[slot].reshape(ps, 1)

            def k_head(h):
                x = _head(k_page, h).astype(jnp.float32)
                if quant:
                    x = x * kscale
                return _lp(x)

            def v_head(h):
                if latent:
                    return _lp(_val(k_page).astype(jnp.float32))
                x = v_page[:, h, :].astype(jnp.float32)
                if quant:
                    x = x * vscale
                return _lp(x)

            logits = jnp.stack([
                jax.lax.dot_general(
                    q_f32[h], k_head(h),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for h in range(kvh)
            ])                                      # [KVH, R, ps]
            if softcap:
                logits = softcap * jnp.tanh(logits / softcap)
            pos = p * ps + jax.lax.broadcasted_iota(
                jnp.int32, (kvh, r, ps), 2
            )
            valid = (pos < ctx_limit) & (
                (window <= 0) | (q_abs[None, :, None] - pos < window)
            )
            logits = jnp.where(valid, logits, _NEG_INF)

            m_new = jnp.maximum(m, logits.max(axis=2, keepdims=True))
            alpha = jnp.exp(m - m_new)
            prob = jnp.exp(logits - m_new)
            l_new = l * alpha + prob.sum(axis=2, keepdims=True)
            acc_new = acc * alpha + jnp.stack([
                jax.lax.dot_general(
                    prob[h], v_head(h),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for h in range(kvh)
            ])
            return m_new, l_new, acc_new

        return jax.lax.fori_loop(p0, n_pages, body, carry)

    def chunk_tile():
        start = scal_ref[2]
        total = scal_ref[3]
        r = bq * g
        q_heads = [_lp(qc_ref[h].astype(jnp.float32) * scale)
                   for h in range(kvh)]             # KVH x [R, D]
        # row → chunk-relative token index (rows are token-major: g rows
        # per token)
        q_rel = i * bq + jax.lax.broadcasted_iota(jnp.int32, (r,), 0) // g
        q_abs = start + q_rel

        m0 = jnp.full((kvh, r, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((kvh, r, 1), jnp.float32)
        acc0 = jnp.zeros((kvh, r, dop), jnp.float32)
        m, l, acc = attend_pages(
            lambda p: crow_ref[p], start, crow_ref.shape[0], q_heads,
            q_abs, start + i * bq, r, (m0, l0, acc0),
        )

        # phase 2: the chunk's own K/V blocks, causal within the chunk
        nkb = pl.cdiv((i + 1) * bq, bk)
        kb0 = jnp.where(
            window > 0, jnp.maximum(i * bq - window + 1, 0) // bk, 0
        )
        kb0 = jnp.minimum(kb0, nkb)

        def chunk_body(kb, carry):
            m, l, acc = carry
            k_blk = kc_ref[pl.ds(kb * bk, bk)]      # [BK, KVH, D]
            v_blk = (_val(k_blk) if latent
                     else vc_ref[pl.ds(kb * bk, bk)])
            logits = jnp.stack([
                jax.lax.dot_general(
                    q_heads[h], _lp(_head(k_blk, h).astype(jnp.float32)),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for h in range(kvh)
            ])                                      # [KVH, R, BK]
            if softcap:
                logits = softcap * jnp.tanh(logits / softcap)
            krel = kb * bk + jax.lax.broadcasted_iota(
                jnp.int32, (kvh, r, bk), 2
            )
            dist = q_rel[None, :, None] - krel
            valid = (dist >= 0) & (start + krel < total) & (
                (window <= 0) | (dist < window)
            )
            logits = jnp.where(valid, logits, _NEG_INF)

            m_new = jnp.maximum(m, logits.max(axis=2, keepdims=True))
            alpha = jnp.exp(m - m_new)
            prob = jnp.exp(logits - m_new)
            l_new = l * alpha + prob.sum(axis=2, keepdims=True)
            acc_new = acc * alpha + jnp.stack([
                jax.lax.dot_general(
                    prob[h], _lp(_head(v_blk, h).astype(jnp.float32)),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for h in range(kvh)
            ])
            return m_new, l_new, acc_new

        _, l, acc = jax.lax.fori_loop(kb0, nkb, chunk_body, (m, l, acc))
        out = (acc / jnp.maximum(l, 1e-30))[..., :do]  # [KVH, R, D]
        oc_ref[...] = out.astype(oc_ref.dtype)

    def group_tile():
        s = i - nct if has_chunk else i
        length = lens_ref[s]
        r = td * g
        q_heads = [_lp(qg_ref[0, h].astype(jnp.float32) * scale)
                   for h in range(kvh)]             # KVH x [R, D]
        tok = jax.lax.broadcasted_iota(jnp.int32, (r,), 0) // g
        if has_tree:
            # tree verify (ISSUE 18): row token i's LOGICAL position is
            # length + depth[i] (its storage position stays length + i).
            # The topology rides in as two static-length scalar-prefetch
            # rows, spread over the rows/columns by td unrolled selects
            # (td <= 32) — Mosaic has no [Td, G] -> [Td*G] shape cast.
            def per_node(ref, idx):
                out = jnp.zeros(idx.shape, jnp.int32)
                for j in range(td):
                    out = jnp.where(idx == j, ref[j], out)
                return out

            row_depth = per_node(tpos_ref, tok)
            q_abs = length + row_depth
        else:
            q_abs = length + tok

        m0 = jnp.full((kvh, r, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((kvh, r, 1), jnp.float32)
        acc0 = jnp.zeros((kvh, r, dop), jnp.float32)
        m, l, acc = attend_pages(
            lambda p: gtable_ref[s, p], length, gtable_ref.shape[1],
            q_heads, q_abs, length, r, (m0, l0, acc0),
        )

        # merge the Td fresh columns (candidates not yet in the pool):
        # column j is the slot's token at absolute position length + j;
        # row token i attends columns j <= i (verify causality; Td = 1
        # degenerates to the decode kernel's single current-token merge)
        kg = kg_ref[0].astype(jnp.float32)          # [Td, KVH, D]
        vg = _val(kg) if latent else vg_ref[0].astype(jnp.float32)
        logits = jnp.stack([
            jax.lax.dot_general(
                q_heads[h], _lp(_head(kg, h)),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h in range(kvh)
        ])                                          # [KVH, R, Td]
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        col = jax.lax.broadcasted_iota(jnp.int32, (kvh, r, td), 2)
        if has_tree:
            # fresh column j is tree node j: valid iff ancestor-or-self
            # of the row's node (bit j of the row's ancestor bitmask),
            # windowed on logical (depth) distance — ancestor implies
            # dist >= 0, so no separate causal term
            row_bits = per_node(tbits_ref, tok)
            anc = ((row_bits[None, :, None] >> col) & 1) != 0
            dist = row_depth[None, :, None] - per_node(tpos_ref, col)
            valid = anc & ((window <= 0) | (dist < window))
        else:
            dist = tok[None, :, None] - col
            valid = (dist >= 0) & ((window <= 0) | (dist < window))
        logits = jnp.where(valid, logits, _NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=2, keepdims=True))
        alpha = jnp.exp(m - m_new)
        prob = jnp.exp(logits - m_new)
        l = l * alpha + prob.sum(axis=2, keepdims=True)
        acc = acc * alpha + jnp.stack([
            jax.lax.dot_general(
                prob[h], _lp(_head(vg, h)),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h in range(kvh)
        ])
        out = (acc / jnp.maximum(l, 1e-30))[..., :do]  # [KVH, R, D]
        og_ref[0] = out.astype(og_ref.dtype)

    if has_chunk and has_group:
        @pl.when(i < nct)
        def _():
            chunk_tile()

        @pl.when(i >= nct)
        def _():
            group_tile()
    elif has_chunk:
        chunk_tile()
    else:
        group_tile()


# query rows of one chunk tile of `ragged_attention` (tokens x the query
# heads that share a cache head): 32 heads x 128 tokens is the largest
# tile a cell has run (kimi-linear's latent layers); twice that (64 heads,
# longcat-flash) does not compile for a v5e (149 MB of VMEM, 112 of them
# register spills: tests/test_longcat_flash.py)
_RAGGED_TILE_ROWS = 4096


def _ragged_vmem_limit(ps: int, kvh: int, g: int, d: int, bq: int, c: int,
                       td: int, itemsize: int, pool_itemsize: int) -> int:
    """Scoped-VMEM limit for one ragged_attention launch, from its
    buffers: Mosaic's 16 MiB default is below what a 1024-token chunk at
    llama3.2:3b's widths needs, and a limit that is too low is a compile
    error in the first request. Pipelined blocks are double-buffered;
    the f32 working set (per-head q, one page or key block of K and V,
    logits, probabilities, the accumulator and its rescaled copy) is
    counted at the larger of the two tile kinds. Doubled for what the
    compiler keeps live beyond that, floored at the default and capped
    well under the 128 MiB a v5e core has."""
    dp = -(-d // 128) * 128
    r = max(bq, td) * g
    kcols = max(ps, bq)
    blocks = 2 * itemsize * (
        2 * kvh * bq * g * d          # chunk q + out tiles
        + 2 * c * kvh * d             # resident chunk K + V
        + 2 * kvh * td * g * d        # group q + out tiles
        + 2 * td * kvh * d            # group fresh K + V
    )
    scratch = 2 * 2 * ps * kvh * d * pool_itemsize + 2 * 2 * ps * 4
    work = 4 * (
        kvh * r * dp * 3              # q heads, acc, rescaled acc
        + kvh * r * kcols * 3         # logits, mask/exp temporaries, prob
        + 2 * kvh * kcols * dp        # one page/block of K and V in f32
    )
    need = 2 * (blocks + scratch + work)
    return int(min(max(need, 16 << 20), 96 << 20))


@functools.partial(jax.jit,
                   static_argnames=("page_size", "interpret", "softcap",
                                    "latent_dv"))
def ragged_attention(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_size: int,
    q_chunk: jnp.ndarray | None = None,
    chunk_row: jnp.ndarray | None = None,
    chunk_start: jnp.ndarray | None = None,
    chunk_total: jnp.ndarray | None = None,
    k_chunk: jnp.ndarray | None = None,
    v_chunk: jnp.ndarray | None = None,
    q_group: jnp.ndarray | None = None,
    page_table: jnp.ndarray | None = None,
    group_lengths: jnp.ndarray | None = None,
    k_group: jnp.ndarray | None = None,
    v_group: jnp.ndarray | None = None,
    layer: jnp.ndarray | None = None,
    interpret: bool = False,
    softcap: float = 0.0,
    window: jnp.ndarray | int = 0,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    tree_pos: jnp.ndarray | None = None,
    tree_bits: jnp.ndarray | None = None,
    latent_dv: int = 0,
) -> tuple[jnp.ndarray | None, jnp.ndarray | None]:
    """Kernel form of ops.attention.ragged_paged_attention: ONE launch,
    static grid (C/BQ chunk tiles + S group tiles) serving chunked
    prefill, decode (Td=1), and spec-verify (Td=K+1) at once. See the
    dispatcher's docstring for the region contracts. Interpreted, it
    accepts a pool at a head dim under 128 (the loaded values are
    zero-padded to 128 lanes in-register before every dot); compiled,
    Mosaic refuses the slice of such a page, so the engine hands it a
    lane-padded pool (`engine._pool_head_dim`).

    `latent_dv` > 0: a latent pool (`v_pages`, `v_chunk`, `v_group` all
    None): the one cache head's row is the key and its first `latent_dv`
    values are the value; outputs are `latent_dv` wide."""
    latent = latent_dv > 0
    assert latent == (v_pages is None)
    has_chunk = q_chunk is not None
    has_group = q_group is not None
    assert has_chunk or has_group
    has_tree = tree_pos is not None
    assert not has_tree or has_group
    quant = k_scale is not None
    if k_pages.ndim == 4:
        k_pages = k_pages[None]
        v_pages = None if latent else v_pages[None]
        if quant:
            k_scale = k_scale[None]
            v_scale = v_scale[None]
    if layer is None:
        layer = jnp.int32(0)
    kvh, d = k_pages.shape[-2], k_pages.shape[-1]
    # rows of a latent pool travel without their head axis of one
    # (_ragged_attn_kernel): free reshapes of the pool and the fresh rows
    rows = ((d,) if latent else (kvh, d))
    if latent:
        assert kvh == 1, k_pages.shape
        k_pages = k_pages.reshape(*k_pages.shape[:3], d)
        if has_chunk:
            k_chunk = k_chunk.reshape(-1, d)
        if has_group:
            k_group = k_group.reshape(*k_group.shape[:2], d)
    h = (q_chunk if has_chunk else q_group).shape[-2]
    g = h // kvh
    dtype = (q_chunk if has_chunk else q_group).dtype

    nct = 0
    c = bq = bk = 0
    if has_chunk:
        c = q_chunk.shape[1]
        # a chunk tile is bq tokens x g query heads a cache head: at most
        # _RAGGED_TILE_ROWS query rows (64 heads on one latent row: 64
        # tokens a tile; at 8,192 rows Mosaic's spills pass the VMEM)
        bq = min(128, c, max(8, _RAGGED_TILE_ROWS // g))
        bk = min(128, c)
        assert c % bq == 0 and c % bk == 0, (c, bq, bk)
        nct = c // bq
    s = td = 0
    if has_group:
        s, td = q_group.shape[:2]

    kernel = functools.partial(
        _ragged_attn_kernel, ps=page_size, bq=bq, bk=bk, c=c, kvh=kvh,
        g=g, d=d, td=td, nct=nct, softcap=softcap,
        has_chunk=has_chunk, has_group=has_group, quant=quant,
        has_tree=has_tree, dv=latent_dv,
    )

    scal = jnp.stack([
        jnp.asarray(layer, jnp.int32).reshape(()),
        jnp.asarray(window, jnp.int32).reshape(()),
        (jnp.asarray(chunk_start, jnp.int32).reshape(())
         if has_chunk else jnp.int32(0)),
        (jnp.asarray(chunk_total, jnp.int32).reshape(())
         if has_chunk else jnp.int32(0)),
    ])

    prefetch: list = [scal]
    if has_group:
        prefetch += [group_lengths.astype(jnp.int32),
                     page_table.astype(jnp.int32)]
    if has_tree:
        prefetch += [tree_pos.astype(jnp.int32),
                     tree_bits.astype(jnp.int32)]
    if has_chunk:
        prefetch += [chunk_row.astype(jnp.int32)]

    # Queries and outputs travel kv-head-major — [..., KVH, tokens*G, D],
    # a token's G query heads on adjacent rows — so each kv head's rows
    # are one aligned [R, D] slab for the per-head dots. A token-major
    # [tokens, KVH, G, D] block pads every G-row group to a whole sublane
    # tile in VMEM (5x for G = 3: 20 MB of blocks at C = 1024, past the
    # scoped limit) and needs a 4-D transpose in the kernel.
    def heads_major(x):      # [..., T, H, D] -> [..., KVH, T*G, D]
        *lead, t, _, _ = x.shape
        x = jnp.moveaxis(x.reshape(*lead, t, kvh, g, d), -3, -4)
        return x.reshape(*lead, kvh, t * g, d)

    do = latent_dv or d      # output (value) width

    def tokens_major(x):     # [..., KVH, T*G, Do] -> [..., T, H, Do]
        *lead, _, tg, _ = x.shape
        x = jnp.moveaxis(x.reshape(*lead, kvh, tg // g, g, do), -4, -3)
        return x.reshape(*lead, tg // g, h, do)

    # block index clamps: chunk operands pin to their last tile during
    # group steps (and vice versa at index 0) — those blocks are simply
    # not re-fetched/written outside their region
    last_ct = max(nct - 1, 0)

    def chunk_q_spec(w=d):
        return pl.BlockSpec(
            (kvh, bq * g, w),
            lambda i, *_: (0, jnp.minimum(i, last_ct), 0),
            memory_space=pltpu.VMEM)

    def group_q_spec(w=d):
        return pl.BlockSpec(
            (1, kvh, td * g, w),
            lambda i, *_: (jnp.maximum(i - nct, 0), 0, 0, 0),
            memory_space=pltpu.VMEM)

    in_specs = []
    args = []
    n_kv = 1 if latent else 2    # fresh-row and pool operands a region
    if has_chunk:
        in_specs += [chunk_q_spec()] + [
            pl.BlockSpec((c, *rows), lambda i, *_: (0,) * (1 + len(rows)),
                         memory_space=pltpu.VMEM),
        ] * n_kv
        args += [heads_major(q_chunk[0]), k_chunk, v_chunk][:1 + n_kv]
    if has_group:
        def _gidx(i, *_):
            return (jnp.maximum(i - nct, 0), 0) + (0,) * len(rows)

        in_specs += [group_q_spec()] + [
            pl.BlockSpec((1, td, *rows), _gidx, memory_space=pltpu.VMEM),
        ] * n_kv
        args += [heads_major(q_group), k_group, v_group][:1 + n_kv]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * n_kv
    args += [k_pages, v_pages][:n_kv]
    if quant:
        # int8 pool (ISSUE 11): per-row scales stay in HBM and are DMA'd
        # page-by-page next to the value pages (dequant epilogue)
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

    out_specs = []
    out_shape = []
    if has_chunk:
        out_specs.append(chunk_q_spec(do))
        out_shape.append(jax.ShapeDtypeStruct((kvh, c * g, do), dtype))
    if has_group:
        out_specs.append(group_q_spec(do))
        out_shape.append(jax.ShapeDtypeStruct((s, kvh, td * g, do), dtype))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(nct + s,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, page_size, *rows), k_pages.dtype),
        ] * n_kv + [
            pltpu.SemaphoreType.DMA((2, 2)),
        ] + ([
            pltpu.VMEM((2, page_size), jnp.float32),
            pltpu.VMEM((2, page_size), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ] if quant else []),
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_ragged_vmem_limit(
                page_size, kvh, g, d, bq, c, td, dtype.itemsize,
                k_pages.dtype.itemsize)),
    )(*prefetch, *args)
    it = iter(outs)
    out_chunk = out_group = None
    if has_chunk:
        out_chunk = tokens_major(next(it))[None]
    if has_group:
        out_group = tokens_major(next(it))
    return out_chunk, out_group


# ---------------------------------------------------------------------------
# paged KV writes (in-place DMA; replaces XLA scatter on the hot path)
# ---------------------------------------------------------------------------
#
# XLA lowers the jnp scatter form of the page-pool update to a serialized
# scatter costing ~12 ms/step (decode) and ~18 ms/prefill for a 3B model on
# v5e — measured dominant over the attention math itself (round-4
# profiling). Worse, updating per-layer pool slices INSIDE the layer scan
# defeats input/output buffer aliasing, adding full-pool copies. These
# kernels run ONCE per jitted step, at top level, over all layers — where
# jit donation guarantees a true in-place update — and DMA exactly the
# written rows/pages.


def _write_decode_all_kernel(
    page_idx_ref,  # SMEM prefetch: [S] destination page per slot (P = skip)
    offset_ref,    # SMEM prefetch: [S] row within the page
    k_new_ref,     # VMEM (1, S, KVH, D) — this layer's new rows
    v_new_ref,
    k_in,          # ANY [L, P, ps, KVH, D] — aliased with k_out
    v_in,
    k_out,
    v_out,
    sems,          # DMA sems [S, 2]
    *, num_pages: int, s: int,
):
    del k_in, v_in  # alias of the outputs; only written here
    layer = pl.program_id(0)
    for i in range(s):  # static unroll: all slots' DMAs go out together
        page = page_idx_ref[i]
        off = offset_ref[i]

        @pl.when(page < num_pages)
        def _(i=i, page=page, off=off):
            pltpu.make_async_copy(
                k_new_ref.at[0, i], k_out.at[layer, page, off], sems.at[i, 0]
            ).start()
            pltpu.make_async_copy(
                v_new_ref.at[0, i], v_out.at[layer, page, off], sems.at[i, 1]
            ).start()

    for i in range(s):
        page = page_idx_ref[i]

        @pl.when(page < num_pages)
        def _(i=i, page=page):
            # wait descriptors must match the started copies' shapes
            off = offset_ref[i]
            pltpu.make_async_copy(
                k_new_ref.at[0, i], k_out.at[layer, page, off], sems.at[i, 0]
            ).wait()
            pltpu.make_async_copy(
                v_new_ref.at[0, i], v_out.at[layer, page, off], sems.at[i, 1]
            ).wait()


# rows one call of paged_write_decode writes (two DMA semaphores each)
_WRITE_ROWS = 128


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_write_decode(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    page_idx: jnp.ndarray,
    offset: jnp.ndarray,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write one [KVH, D] row per (layer, slot) into the page pool, in place.

    k_pages/v_pages: [L, P, ps, KVH, D] (the FULL pool, all layers);
    k_new/v_new: [L, S, KVH, D]; page_idx: [S] destination page id with the
    out-of-bounds sentinel `num_pages` meaning "skip this slot" (inactive /
    past capacity / unmapped — the hazards ops.kvcache._safe_page_idx masks
    for the scatter path); offset: [S] row within the page. Pages are
    slot-exclusive, so rows never collide.

    The pools are input_output_aliased; under jit+donation this is a true
    in-place update — HBM traffic is just the written rows (~L*S*KVH*D*2
    bytes per step).
    """
    L, _, _, kvh, d = k_pages.shape
    s = k_new.shape[1]
    if s > _WRITE_ROWS:
        # a DMA semaphore a row and pool: 48 slots x 5 verify rows ask
        # for 480, past the chip's semaphore memory (compiled for a v5e:
        # "ran out of memory in memory space sflag", PR 61). The rows in
        # calls of at most _WRITE_ROWS; each updates the pools in place
        for a in range(0, s, _WRITE_ROWS):
            b = min(a + _WRITE_ROWS, s)
            k_pages, v_pages = paged_write_decode(
                k_pages, v_pages, k_new[:, a:b], v_new[:, a:b],
                page_idx[a:b], offset[a:b], interpret=interpret)
        return k_pages, v_pages
    num_pages = k_pages.shape[1]
    kernel = functools.partial(
        _write_decode_all_kernel, num_pages=num_pages, s=s
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L,),
        in_specs=[
            pl.BlockSpec((1, s, kvh, d), lambda l, *_: (l, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s, kvh, d), lambda l, *_: (l, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[pltpu.SemaphoreType.DMA((s, 2))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # inputs are numbered across (scalar prefetch ops, tensor ops):
        # 0: page_idx, 1: offset, 2: k_new, 3: v_new, 4: k_pages, 5: v_pages
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(
        page_idx.astype(jnp.int32), offset.astype(jnp.int32),
        k_new, v_new, k_pages, v_pages,
    )


def _write_chunk_all_kernel(
    dst_pages_ref,  # SMEM prefetch: [T//ps] destination page per chunk page
    *refs,          # n x new rows VMEM (1, ps, KVH, D), n x pool in (ANY,
                    # aliased with) n x pool out, DMA sems [n]
    num_pages: int, n: int,
):
    new_refs, outs, sems = refs[:n], refs[2 * n:3 * n], refs[3 * n]
    layer = pl.program_id(0)
    c = pl.program_id(1)
    page = dst_pages_ref[c]

    @pl.when(page < num_pages)
    def _():
        copies = [pltpu.make_async_copy(
            new.at[0], out.at[layer, page], sems.at[j])
            for j, (new, out) in enumerate(zip(new_refs, outs))]
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_write_chunk(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray | None,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray | None,
    table_row: jnp.ndarray,
    start: jnp.ndarray,
    length: jnp.ndarray,
    page_size: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """Write a prefill chunk's K/V (all layers) into one slot's pages,
    in place.

    k_pages/v_pages: [L, P, ps, KVH, D]; k_new/v_new: [L, T, KVH, D] with
    T % page_size == 0 (a latent pool: `v_pages` and `v_new` None).
    `start` (the absolute position of row 0) must be
    page-aligned — a traced value the engine guarantees: fresh prefills
    start at 0 and chunked prefill chunks at multiples of prefill_chunk,
    which EngineConfig rounds to a multiple of the page size.

    Whole pages are DMA'd, including the padding tail of the last partial
    page: padded rows land in pages this slot owns (capacity ≥ length) and
    attention masks positions ≥ length, so the garbage is never read — and
    a later chunk overwrites it with real data. Pages fully past `length`
    (bucket padding) and unmapped (-1) entries are skipped.
    """
    L, _, _, kvh, d = k_pages.shape
    t = k_new.shape[1]
    assert t % page_size == 0, (t, page_size)
    n_chunk_pages = t // page_size
    num_pages = k_pages.shape[1]
    pools = [k_pages] + ([] if v_pages is None else [v_pages])
    news = [k_new] + ([] if v_pages is None else [v_new])
    n = len(pools)
    rows = (kvh, d)
    if v_pages is None:
        # a latent pool's pages travel as [ps, D]: its head axis of one
        # would be padded to the sublane tile (_ragged_attn_kernel)
        rows = (d,)
        pools = [k_pages.reshape(*k_pages.shape[:3], d)]
        news = [k_new.reshape(L, t, d)]

    first_page = start // page_size
    c = jnp.arange(n_chunk_pages, dtype=jnp.int32)
    idx = jnp.minimum(first_page + c, table_row.shape[0] - 1)
    mapped = table_row[idx]
    covered = c * page_size < length  # page holds at least one valid row
    dst = jnp.where(covered & (mapped >= 0), mapped, num_pages).astype(jnp.int32)

    kernel = functools.partial(_write_chunk_all_kernel, num_pages=num_pages,
                               n=n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L, n_chunk_pages),
        in_specs=[
            pl.BlockSpec((1, page_size, *rows),
                         lambda l, c, *_: (l, c) + (0,) * len(rows),
                         memory_space=pltpu.VMEM)
        ] * n + [pl.BlockSpec(memory_space=pl.ANY)] * n,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
        scratch_shapes=[pltpu.SemaphoreType.DMA((n,))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # 0: dst pages (prefetch), then the n new rows, then the n pools
        input_output_aliases={1 + n + j: j for j in range(n)},
        interpret=interpret,
    )(dst, *news, *pools)
    if v_pages is None:
        return out[0].reshape(k_pages.shape), None
    return out[0], out[1]


# ---------------------------------------------------------------------------
# gated delta rule (ops/linear_attn.py holds the mathematics and the oracle)
# ---------------------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST


def _gdn_block(s, wv, wk, aqk, qg, kd, gc, pack: int, dv: int):
    """One block of rows on the state of `pack` heads side by side.
    s [dk, pack*dv] float32; wv [C, pack*dv] and gc [1, pack*dv] packed as
    the state is; wk, qg, kd [pack, C, dk] and aqk [pack, C, C] a head.
    Returns (o [C, pack*dv], the state after): U = wv - wk S,
    O = qg S + aqk U, S' = gc S + kd^T U, each head's product taken at the
    packed width and kept on its own lanes (no slice at a lane offset
    that is not a tile's). A decay a key channel (Kimi Delta Attention)
    hands gc a head as a row [pack, 1, dk]: each ROW of a head's state
    decays at its own rate, S' = Diag(gc) S + kd^T U, a product like the
    others (the diagonal built from the row: no transpose)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, pack * dv), 1)

    def per_head(f):
        out = None
        for p in range(pack):
            own = (lane >= p * dv) & (lane < (p + 1) * dv)
            term = jnp.where(own, f(p), 0.0)
            out = term if out is None else out + term
        return out

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32,
                       precision=_HI)

    def decayed():
        if gc.ndim == 2:
            return s * gc
        dk = s.shape[0]
        on_diag = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
                   == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
        return per_head(lambda p: dot(
            jnp.where(on_diag, jnp.broadcast_to(gc[p], (dk, dk)), 0.0), s))

    u = wv - per_head(lambda p: dot(wk[p], s))
    o = per_head(lambda p: dot(qg[p], s) + dot(aqk[p], u))
    s = decayed() + per_head(lambda p: jax.lax.dot_general(
        kd[p], u, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HI))
    return o, s


def _gdn_chunk_kernel(keep_ref, s0_ref, wv_ref, wk_ref, aqk_ref, qg_ref,
                      kd_ref, gc_ref, o_ref, s_ref, kept_ref, acc, *,
                      pack: int, dv: int, n_keep: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        acc[...] = s0_ref[...]
        kept_ref[...] = jnp.zeros_like(kept_ref)

    o, s = _gdn_block(acc[...], wv_ref[...], wk_ref[...], aqk_ref[...],
                      qg_ref[...], kd_ref[...], gc_ref[...], pack, dv)
    o_ref[...] = o
    acc[...] = s
    for j in range(n_keep):
        @pl.when(keep_ref[j] == i)
        def _(j=j):
            kept_ref[j] = s

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        s_ref[...] = s


def gdn_chunk(state, wy, keep, *, heads: int, interpret: bool = False,
              name: str = "gdn_chunk"):
    """The blocks of ONE slot's rows chained through its state.
    state [dk, H*dv] float32 (packed); wy: ops.linear_attn._lanes(_wy(..))
    with leading [nb]; keep [n] int32 block indices whose end state is
    handed back (-1: zeros). Returns (o [nb, C, H*dv], the state after,
    kept [n, dk, H*dv]). Grid (head packs, blocks): the state of a pack
    stays in VMEM across its blocks. `name` is the custom call's (a decay
    a key channel, gc [nb, H, 1, dk], runs as "kda_chunk")."""
    from gridllm_tpu.ops.linear_attn import head_pack

    dk, hd = state.shape
    dv = hd // heads
    pack = head_pack(dv, heads)
    nb, c = wy["wv"].shape[:2]
    n_keep = keep.shape[0]
    lanes = pack * dv

    def per_head(width):
        return pl.BlockSpec((None, pack, c, width),
                            lambda h, i, *_: (i, h, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(heads // pack, nb),
        in_specs=[
            pl.BlockSpec((dk, lanes), lambda h, i, *_: (0, h)),
            pl.BlockSpec((None, c, lanes), lambda h, i, *_: (i, 0, h)),
            per_head(dk), per_head(c), per_head(dk), per_head(dk),
            pl.BlockSpec((None, 1, lanes), lambda h, i, *_: (i, 0, h))
            if wy["gc"].ndim == 3 else pl.BlockSpec(
                (None, pack, 1, dk), lambda h, i, *_: (i, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, c, lanes), lambda h, i, *_: (i, 0, h)),
            pl.BlockSpec((dk, lanes), lambda h, i, *_: (0, h)),
            pl.BlockSpec((n_keep, dk, lanes), lambda h, i, *_: (0, 0, h)),
        ],
        scratch_shapes=[pltpu.VMEM((dk, lanes), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gdn_chunk_kernel, pack=pack, dv=dv, n_keep=n_keep),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nb, c, hd), jnp.float32),
            jax.ShapeDtypeStruct((dk, hd), jnp.float32),
            jax.ShapeDtypeStruct((n_keep, dk, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(keep.astype(jnp.int32), state, wy["wv"], wy["wk"], wy["aqk"],
      wy["qg"], wy["kd"], wy["gc"])


def _gdn_step_kernel(layer_ref, order_ref, live_ref, s_in, wv_ref, wk_ref,
                     aqk_ref, qg_ref, kd_ref, gc_ref, s_out, o_ref, *,
                     pack: int, dv: int):
    del layer_ref, order_ref         # read by the index maps
    n_live = live_ref[0]

    @pl.when(pl.program_id(1) < n_live)
    def _():
        # block 0: the last launch's rows that were kept, committed and
        # written back; block 1: this launch's rows, run on top, not written
        _, s = _gdn_block(s_in[...], wv_ref[0], wk_ref[0], aqk_ref[0],
                          qg_ref[0], kd_ref[0], gc_ref[0], pack, dv)
        s_out[...] = s
        o, _ = _gdn_block(s, wv_ref[1], wk_ref[1], aqk_ref[1], qg_ref[1],
                          kd_ref[1], gc_ref[1], pack, dv)
        o_ref[...] = o

    @pl.when(n_live == 0)
    def _():
        # nothing is live: every step visits the first slot's block, which
        # goes back as it came
        s_out[...] = s_in[...]


def gdn_step(states, layer, order, n_live, wy, *, heads: int,
             interpret: bool = False, name: str = "gdn_step"):
    """Every LIVE slot's pending rows committed and its new rows run.
    states [Ll, S, dk, H*dv] float32 (every linear layer; `layer` picks,
    updated IN PLACE: input_output_aliases); order [S] int32 the slots,
    live ones first, n_live of them; wy: _lanes(_wy(..)) with leading
    [S, 2] (pending block, new block), rows padded to whole sublane tiles.
    Returns (states, o [S, C, H*dv] of the new block; junk for a slot
    that is not live). Grid (head packs, slots in `order`): one read and
    one write of each live slot's state; a step past the live ones names
    the last live slot's blocks again, which moves nothing."""
    from gridllm_tpu.ops.linear_attn import head_pack

    _, slots, dk, hd = states.shape
    dv = hd // heads
    pack = head_pack(dv, heads)
    c = wy["wv"].shape[2]
    lanes = pack * dv

    def slot(g, order, live):
        return order[jnp.minimum(g, jnp.maximum(live[0] - 1, 0))]

    def per_head(width):
        return pl.BlockSpec(
            (None, 2, pack, c, width),
            lambda h, g, li, order, live: (slot(g, order, live), 0, h, 0, 0))

    def packed(rows):
        return pl.BlockSpec(
            (None, 2, rows, lanes),
            lambda h, g, li, order, live: (slot(g, order, live), 0, 0, h))

    state_spec = pl.BlockSpec(
        (None, None, dk, lanes),
        lambda h, g, li, order, live: (li[0], slot(g, order, live), 0, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(heads // pack, slots),
        in_specs=[state_spec, packed(c), per_head(dk), per_head(c),
                  per_head(dk), per_head(dk),
                  # the decay: packed on the lanes, or a key row a head
                  packed(1) if wy["gc"].ndim == 4 else pl.BlockSpec(
                      (None, 2, pack, 1, dk),
                      lambda h, g, li, order, live: (
                          slot(g, order, live), 0, h, 0, 0))],
        out_specs=[
            state_spec,
            pl.BlockSpec(
                (None, c, lanes),
                lambda h, g, li, order, live: (slot(g, order, live), 0, h)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_gdn_step_kernel, pack=pack, dv=dv),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(states.shape, jnp.float32),
            jax.ShapeDtypeStruct((slots, c, hd), jnp.float32),
        ],
        # 0: layer, 1: order, 2: n_live, 3: states, 4..: the blocks' arrays
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1), order.astype(jnp.int32),
      jnp.asarray(n_live, jnp.int32).reshape(1), states, wy["wv"], wy["wk"],
      wy["aqk"], wy["qg"], wy["kd"], wy["gc"])


# ---------------------------------------------------------------------------
# the state-space scan (ops/linear_attn.py: `ssd_*`): the rule without the
# delta, keys and queries shared by every head
# ---------------------------------------------------------------------------


def _ssd_read(s, q, eg, own):
    """A block's output on the state before it: (q S) * eg + own. s [dk,
    L] float32 (a lane tile of the packed state); q [C, dk]; eg, own [C,
    L]. One product at the packed width: q is every head's."""
    return jnp.dot(q, s, preferred_element_type=jnp.float32,
                   precision=_HI) * eg + own


def _ssd_write(s, k, vd, gc):
    """The state after a block: gc * S + k^T vd. k [C, dk]; vd [C, L];
    gc [1, L]."""
    return s * gc + jax.lax.dot_general(
        k, vd, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HI)


def _ssd_chunk_kernel(keep_ref, s0_ref, q_ref, k_ref, eg_ref, own_ref,
                      vd_ref, gc_ref, o_ref, s_ref, kept_ref, acc, *,
                      n_keep: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        acc[...] = s0_ref[...]
        kept_ref[...] = jnp.zeros_like(kept_ref)

    s = acc[...]
    o_ref[...] = _ssd_read(s, q_ref[...], eg_ref[...], own_ref[...])
    s = _ssd_write(s, k_ref[...], vd_ref[...], gc_ref[...])
    acc[...] = s
    for j in range(n_keep):
        @pl.when(keep_ref[j] == i)
        def _(j=j):
            kept_ref[j] = s

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        s_ref[...] = s


def ssd_chunk(state, blocks, keep, *, interpret: bool = False):
    """The blocks of ONE slot's rows chained through its state. state
    [dk, H*dv] float32 (packed); blocks: ops.linear_attn._ssd_blocks(..)
    with leading [nb]; keep [n] int32 block indices whose end state is
    handed back (-1: zeros). Returns (o [nb, C, H*dv], the state after,
    kept [n, dk, H*dv]). Grid (lane tiles, blocks): a tile of the state
    stays in VMEM across its blocks; q and k are read once a tile."""
    from gridllm_tpu.ops.linear_attn import ssd_lane_tile

    dk, hd = state.shape
    lanes = ssd_lane_tile(hd)
    nb, c = blocks["q"].shape[:2]
    n_keep = keep.shape[0]
    shared = pl.BlockSpec((None, c, dk), lambda h, i, *_: (i, 0, 0))
    packed = pl.BlockSpec((None, c, lanes), lambda h, i, *_: (i, 0, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hd // lanes, nb),
        in_specs=[
            pl.BlockSpec((dk, lanes), lambda h, i, *_: (0, h)),
            shared, shared, packed, packed, packed,
            pl.BlockSpec((None, 1, lanes), lambda h, i, *_: (i, 0, h)),
        ],
        out_specs=[
            packed,
            pl.BlockSpec((dk, lanes), lambda h, i, *_: (0, h)),
            pl.BlockSpec((n_keep, dk, lanes), lambda h, i, *_: (0, 0, h)),
        ],
        scratch_shapes=[pltpu.VMEM((dk, lanes), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, n_keep=n_keep),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nb, c, hd), jnp.float32),
            jax.ShapeDtypeStruct((dk, hd), jnp.float32),
            jax.ShapeDtypeStruct((n_keep, dk, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk",
    )(keep.astype(jnp.int32), state, blocks["q"], blocks["k"], blocks["eg"],
      blocks["own"], blocks["vd"], blocks["gc"])


def _ssd_step_kernel(layer_ref, order_ref, live_ref, s_in, pk_ref, pvd_ref,
                     pgc_ref, q_ref, eg_ref, own_ref, s_out, o_ref):
    del layer_ref, order_ref         # read by the index maps
    n_live = live_ref[0]

    @pl.when(pl.program_id(1) < n_live)
    def _():
        # the last launch's rows that were kept, committed and written
        # back; this launch's rows read on top, not written
        s = _ssd_write(s_in[...], pk_ref[...], pvd_ref[...], pgc_ref[...])
        s_out[...] = s
        o_ref[...] = _ssd_read(s, q_ref[...], eg_ref[...], own_ref[...])

    @pl.when(n_live == 0)
    def _():
        # nothing is live: every step visits the first slot's block, which
        # goes back as it came
        s_out[...] = s_in[...]


def ssd_step(states, layer, order, n_live, old, new, *,
             interpret: bool = False):
    """Every LIVE slot's pending rows committed and its new rows read.
    states [Ll, S, dk, H*dv] float32 (every linear layer; `layer` picks,
    updated IN PLACE: input_output_aliases); order [S] int32 the slots,
    live ones first, n_live of them; old, new: _ssd_blocks(..) of the
    pending and the new rows with leading [S], rows padded to whole
    sublane tiles. Returns (states, o [S, C, H*dv] of the new rows; junk
    for a slot that is not live). Grid (lane tiles, slots in `order`): one
    read and one write of each live slot's state; a step past the live
    ones names the last live slot's blocks again, which moves nothing."""
    from gridllm_tpu.ops.linear_attn import ssd_lane_tile

    _, slots, dk, hd = states.shape
    lanes = ssd_lane_tile(hd)
    c = new["q"].shape[1]

    def slot(g, order, live):
        return order[jnp.minimum(g, jnp.maximum(live[0] - 1, 0))]

    def shared():
        return pl.BlockSpec(
            (None, c, dk),
            lambda h, g, li, order, live: (slot(g, order, live), 0, 0))

    def packed(rows):
        return pl.BlockSpec(
            (None, rows, lanes),
            lambda h, g, li, order, live: (slot(g, order, live), 0, h))

    state_spec = pl.BlockSpec(
        (None, None, dk, lanes),
        lambda h, g, li, order, live: (li[0], slot(g, order, live), 0, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hd // lanes, slots),
        in_specs=[state_spec, shared(), packed(c), packed(1),
                  shared(), packed(c), packed(c)],
        out_specs=[state_spec, packed(c)],
    )
    return pl.pallas_call(
        _ssd_step_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(states.shape, jnp.float32),
            jax.ShapeDtypeStruct((slots, c, hd), jnp.float32),
        ],
        # 0: layer, 1: order, 2: n_live, 3: states, 4..: the blocks' arrays
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), order.astype(jnp.int32),
      jnp.asarray(n_live, jnp.int32).reshape(1), states, old["k"], old["vd"],
      old["gc"], new["q"], new["eg"], new["own"])


# ---------------------------------------------------------------------------
# routed experts: the touched experts' products (models/mixtral.py's third
# form, `grouped`)
# ---------------------------------------------------------------------------
#
# Under the chip's ridge (240 rows) the routed products are bound by the
# bytes of the weights they read, and the all-experts einsum reads every
# expert held whatever the rows picked. This kernel reads an expert's three
# slabs once if a live row picked it and not at all otherwise: the rows are
# neither sorted nor gathered (all T rows times one expert's slabs is less
# arithmetic than the slabs' bytes take to arrive), a row that did not pick
# the expert has a gate of zero.

# one step's three slabs, double-buffered, may take this much VMEM: whole
# experts at every accepted width but mixtral's (117 MB a slab: F-tiles)
_EXPERT_TILE_BYTES = 40 << 20


def _expert_tile(e: int, f: int, itemsize: int) -> int:
    """Columns of F a step takes: F whole, or its largest divisor that is
    a multiple of 128 lanes whose three double-buffered slabs fit
    `_EXPERT_TILE_BYTES` (F whole where there is none)."""
    if 6 * e * f * itemsize <= _EXPERT_TILE_BYTES:
        return f
    return max((d for d in range(128, f, 128) if f % d == 0
                and 6 * e * d * itemsize <= _EXPERT_TILE_BYTES), default=f)


def _grouped_experts_kernel(layer_ref, touched_ref, x_ref, gates_ref, wg_hbm,
                            wu_hbm, wd_hbm, o_ref, ids, gbuf, ubuf, dbuf, sem,
                            acc, *, nf: int, tf: int, act: str, precision):
    li = layer_ref[0]

    def pick(e, n):          # the touched experts' ids, compacted
        ids[n] = e
        return n + (touched_ref[e] > 0).astype(jnp.int32)

    steps = nf * jax.lax.fori_loop(0, touched_ref.shape[0], pick,
                                   jnp.int32(0))

    def copies(step, slot):
        e = ids[step // nf]
        cols = (slice(None) if nf == 1
                else pl.ds(pl.multiple_of((step % nf) * tf, 128), tf))
        return (
            pltpu.make_async_copy(wg_hbm.at[li, e, :, cols], gbuf.at[slot],
                                  sem.at[0, slot]),
            pltpu.make_async_copy(wu_hbm.at[li, e, :, cols], ubuf.at[slot],
                                  sem.at[1, slot]),
            pltpu.make_async_copy(wd_hbm.at[li, e, cols, :], dbuf.at[slot],
                                  sem.at[2, slot]))

    acc[...] = jnp.zeros_like(acc)

    @pl.when(steps > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    experts = jax.lax.broadcasted_iota(jnp.int32, gates_ref.shape, 1)
    silu = act == "silu"

    def one(step, carry):
        slot = step % 2

        @pl.when(step + 1 < steps)
        def _():
            for c in copies(step + 1, 1 - slot):
                c.start()

        for c in copies(step, slot):
            c.wait()
        x = x_ref[...]
        g = jnp.dot(x, gbuf[slot], precision=precision,
                    preferred_element_type=jnp.float32)
        u = jnp.dot(x, ubuf[slot], precision=precision,
                    preferred_element_type=jnp.float32)
        # the rows' gates of this expert: its column of [T, X]
        gate = jnp.sum(
            jnp.where(experts == ids[step // nf], gates_ref[...], 0.0),
            axis=1, keepdims=True)
        g = g * jax.nn.sigmoid(g) if silu else jnp.maximum(g, 0.0)
        y = (g * u * gate).astype(dbuf.dtype)
        acc[...] += jnp.dot(y, dbuf[slot], precision=precision,
                            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, steps, one, 0)
    o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "interpret", "tile_f"))
def grouped_experts(x, gates, touched, wg, wu, wd, layer=None, *, act: str,
                    interpret: bool = False, tile_f: int | None = None):
    """sum over the TOUCHED experts j of (act(x wg[j]) * (x wu[j]) *
    gates[:, j]) wd[j]: x [T, E]; gates [T, X] float32, zero where a row
    did not pick the expert or is not live; touched [X] int32, nonzero
    where a live row picked it; wg, wu [L, X, E, F] and wd [L, X, F, E] as
    they are stored, every layer's (`layer` picks; the stack stays in HBM,
    a slice of it handed over would be copied), or one layer's [X, ..].
    Operands as they come (bfloat16), float32 sums over F and over
    experts, one cast at the end. One expert's slabs (or F-tiles of them,
    `_expert_tile`) arrive by double-buffered DMA while the last ones
    multiply; an expert that no live row picked is not read, and with none
    touched nothing is and the output is zeros. The custom call is named
    after this function (benchmark/readers.py GROUPED_OPS)."""
    if wg.ndim == 3:
        wg, wu, wd = wg[None], wu[None], wd[None]
    if layer is None:
        layer = jnp.int32(0)
    t, e = x.shape
    nx, f = wg.shape[1], wg.shape[3]
    tf = tile_f or _expert_tile(e, f, wg.dtype.itemsize)
    assert f % tf == 0 and (tf == f or tf % 128 == 0), (f, tf)
    rows = -(-t // 16) * 16          # whole sublane tiles of bfloat16
    if rows != t:
        x = jnp.pad(x, ((0, rows - t), (0, 0)))
        gates = jnp.pad(gates, ((0, rows - t), (0, 0)))
    kernel = functools.partial(
        _grouped_experts_kernel, nf=f // tf, tf=tf, act=act,
        precision=(jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                   else None))

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    slabs = 2 * 3 * e * tf * wg.dtype.itemsize
    work = 4 * (3 * rows * e + 4 * rows * tf + rows * nx) + 4 * rows * e * (
        x.dtype.itemsize)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole((rows, e)), whole((rows, nx))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 3,
            out_specs=whole((rows, e)),
            scratch_shapes=[
                pltpu.SMEM((nx,), jnp.int32),
                pltpu.VMEM((2, e, tf), wg.dtype),
                pltpu.VMEM((2, e, tf), wu.dtype),
                pltpu.VMEM((2, tf, e), wd.dtype),
                pltpu.SemaphoreType.DMA((3, 2)),
                pltpu.VMEM((rows, e), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((rows, e), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(min(max(slabs + 2 * work + (4 << 20),
                                         16 << 20), 100 << 20))),
        interpret=interpret,
        name="grouped_experts",
    )(jnp.asarray(layer, jnp.int32).reshape(1), touched.astype(jnp.int32),
      x, gates.astype(jnp.float32), wg, wu, wd)
    return out[:t]


# -- the sorted regime: rows past the chip's ridge (a mixed launch's 528) ------
#
# There the arithmetic of all rows against every touched expert binds, not the
# bytes, so the rows are SORTED by expert first (in XLA: ops/experts.py
# `sorted_layout`) and each expert's slabs meet only the row tiles of its own
# group. A grid over row tiles; a tile's expert comes from scalar prefetch
# and indexes the weights' blocks, so consecutive tiles of one expert fetch
# its slabs once and an expert with no group is never named. The grid's
# extent is the tiles that hold a group (a traced value: the layout's static
# bound would cost a step a tile it never fills). The pipeline fetches the
# next tile's blocks while this one multiplies: with a tile a group the
# slabs' arrival is continuous.


def _sorted_experts_kernel(layer_ref, expert_ref, x_ref, wg_ref, wu_ref,
                           wd_ref, o_ref, *acc, nf: int, act: str, precision):
    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], precision=precision,
                preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], precision=precision,
                preferred_element_type=jnp.float32)
    g = g * jax.nn.sigmoid(g) if act == "silu" else jnp.maximum(g, 0.0)
    d = jnp.dot((g * u).astype(wd_ref.dtype), wd_ref[...],
                precision=precision, preferred_element_type=jnp.float32)
    if nf == 1:
        o_ref[...] = d.astype(o_ref.dtype)
        return
    acc_ref, = acc
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = d

    @pl.when(j > 0)
    def _():
        acc_ref[...] += d

    @pl.when(j == nf - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "act", "interpret",
                                             "tile_f"))
def grouped_experts_sorted(xs, tile_expert, used, wg, wu, wd, layer=None, *,
                           tm: int, act: str, interpret: bool = False,
                           tile_f: int | None = None):
    """act(xs wg[j]) * (xs wu[j]) wd[j] a row tile of `tm` rows, j the
    tile's expert: xs [P, E] the rows GATHERED in the order of their
    experts, each group laid out from a multiple of `tm` (P a multiple of
    `tm`; rows between a group's end and the next tile are padding, whose
    products nobody reads); tile_expert [P / tm] int32 the expert of each
    tile, `used` int32 the tiles that belong to a group: the grid's
    extent, so the tiles from there on (P is a static bound) cost no step
    and are not written; wg, wu, wd and `layer` as `grouped_experts` takes
    them. Returns [P, E] in xs's dtype, unweighted: float32 sums over F
    (F-tiles accumulate in VMEM), one cast. An expert's slabs arrive once
    where its group is one tile or F is whole; with F-tiles a second row
    tile of the group reads them again. The custom call carries
    `grouped_experts`' name: it is the same product to a reader of the
    trace (benchmark/readers.py GROUPED_OPS)."""
    if wg.ndim == 3:
        wg, wu, wd = wg[None], wu[None], wd[None]
    if layer is None:
        layer = jnp.int32(0)
    p, e = xs.shape
    f = wg.shape[3]
    tf = tile_f or _expert_tile(e, f, wg.dtype.itemsize)
    assert f % tf == 0 and (tf == f or tf % 128 == 0), (f, tf)
    assert p % tm == 0 and tm % 8 == 0, (p, tm)
    nf = f // tf
    kernel = functools.partial(
        _sorted_experts_kernel, nf=nf, act=act,
        precision=(jax.lax.Precision.HIGHEST if xs.dtype == jnp.float32
                   else None))

    def rows(i, j, layer, expert):
        return i, 0

    def up(i, j, layer, expert):
        return layer[0], expert[i], 0, j

    def down(i, j, layer, expert):
        return layer[0], expert[i], j, 0

    slabs = 2 * 3 * e * tf * wg.dtype.itemsize
    work = 4 * tm * (3 * e + 4 * tf) + 4 * tm * e * xs.dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(jnp.asarray(used, jnp.int32).reshape(()), nf),
            in_specs=[pl.BlockSpec((tm, e), rows),
                      pl.BlockSpec((None, None, e, tf), up),
                      pl.BlockSpec((None, None, e, tf), up),
                      pl.BlockSpec((None, None, tf, e), down)],
            out_specs=pl.BlockSpec((tm, e), rows),
            scratch_shapes=([pltpu.VMEM((tm, e), jnp.float32)]
                            if nf > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((p, e), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(max(slabs + 2 * work + (4 << 20),
                                         16 << 20), 100 << 20))),
        interpret=interpret,
        name="grouped_experts",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_expert.astype(jnp.int32),
      xs, wg, wu, wd)
