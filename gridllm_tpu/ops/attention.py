"""Attention: causal whole-prompt prefill and ragged paged attention.

Public entry points (`attention_prefill` for an unpaged prompt,
`ragged_paged_attention` for every phase that reads the page pool:
chunked prefill, decode, verify, tree verify) dispatch between the
Pallas TPU kernels (ops/pallas_kernels.py) and the
pure-jnp reference implementations (`*_ref` here) — the jnp versions are
correct on CPU and TPU and are the numerical oracle for the kernels
(tests/test_pallas.py). Softmax is computed in fp32 regardless of input
dtype (bf16 accumulation loses real accuracy at long context).

Kernel selection: env `GRIDLLM_PALLAS` = "auto" (default: kernels on TPU
backends only), "1" (force on), "0" (force off), "interpret" (kernels in
interpreter mode — CPU testing).

GQA convention: q has H heads, k/v have KVH heads, H % KVH == 0; kv heads
are logically repeated H//KVH times (implemented via reshape-grouping, no
materialized repeat).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from gridllm_tpu.analysis import numcheck
from gridllm_tpu.ops.kvcache import (
    QuantPages,
    _env_mode,
    _pallas_mode,
    _shard_map_kernel,
    gather_kv,
    kernel_mesh_axis,
    record_kernel_path,
)

__all__ = [
    "attention_prefill", "ragged_paged_attention",
    "ragged_paged_attention_ref",
    "attention_prefill_ref", "paged_attention_decode_ref",
    "_env_mode", "_pallas_mode",  # re-export: policy lives in ops/kvcache.py
]


_NEG_INF = -1e30


# VMEM budget for flash_prefill's resident per-head K+V (the kernel pins
# [T, D] of each; Mosaic rejects kernels past ~16 MB/core at compile
# time). Buckets past this route to flash_prefill_streamed, which DMAs
# K/V from HBM block-by-block instead of pinning them.
_FLASH_KV_VMEM_CAP = 8 * 1024 * 1024
# The ragged kernel keeps a chunk's fresh K and V resident too, double-
# buffered under a limit it computes from its own buffers
# (pallas_kernels._ragged_vmem_limit, at most 96 MiB of a v5e core's 128):
# 1,024 rows of 32 KV heads of 128 (16 MiB; a model with as many KV heads
# as query heads) compile for the chip (tests/test_olmo_hybrid.py). A
# chunk past this takes the jnp path, and the tripwire says so.
_RAGGED_CHUNK_KV_CAP = 16 * 1024 * 1024


def _lane_pad_qkv(q, k_cur, v_cur, dpool):
    """Pad query + current K/V to a lane-padded pool's head dim (engine
    allocates D=128 pages for d=64 models so qwen2.5-class paths keep the
    kernels — VERDICT r04 #5). q is pre-scaled so the downstream
    rsqrt(dpool) equals rsqrt(d); callers slice outputs back to d. Exact:
    padded k lanes meet zero q lanes in every dot; padded v lanes produce
    zeros that are sliced away."""
    d = q.shape[-1]
    pad = [(0, 0)] * (q.ndim - 1) + [(0, dpool - d)]
    q = jnp.pad(q * jnp.sqrt(jnp.float32(dpool) / d).astype(q.dtype), pad)
    if k_cur is not None:
        cpad = [(0, 0)] * (k_cur.ndim - 1) + [(0, dpool - d)]
        k_cur = jnp.pad(k_cur, cpad)
        if v_cur is not None:      # a latent pool has no fresh V
            v_cur = jnp.pad(v_cur, cpad)
    return q, k_cur, v_cur


def _prefill_kernel(q, k, v, seq_lens, window, *, interpret, softcap):
    """The kernel leg of attention_prefill: d-padding + VMEM routing.
    Shapes may be shard-local (called from inside the meshed shard_map)."""
    from gridllm_tpu.ops import pallas_kernels
    from gridllm_tpu.ops.kvcache import lane_pad_dim

    t, d = q.shape[1], q.shape[3]
    dp = lane_pad_dim(d)  # also in interpret mode, so tests cover it
    if dp != d:
        pad = [(0, 0)] * (q.ndim - 1) + [(0, dp - d)]
        # correct the kernel's rsqrt(dp) scale back to rsqrt(d)
        q = jnp.pad(q * jnp.sqrt(jnp.float32(dp) / d).astype(q.dtype), pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    kv_bytes = 2 * t * dp * q.dtype.itemsize
    fn = (
        pallas_kernels.flash_prefill
        if kv_bytes <= _FLASH_KV_VMEM_CAP
        else pallas_kernels.flash_prefill_streamed
    )
    out = fn(q, k, v, seq_lens, interpret=interpret, softcap=softcap,
             window=window)
    return out[..., :d] if dp != d else out


def attention_prefill(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    seq_lens: jnp.ndarray,
    use_pallas: bool | None = None,
    logit_softcap: float = 0.0,
    window: jnp.ndarray | int = 0,
    mesh=None,
) -> jnp.ndarray:
    """Causal GQA prefill attention (see attention_prefill_ref for the
    contract). Kernel routing (VERDICT r03 weak #6 / next-round #9):

    - per-head K+V within the VMEM budget → flash_prefill (K/V resident);
    - past the budget → flash_prefill_streamed (K/V stream from HBM as a
      grid dimension) — long prefill buckets keep the kernel path;
    - head_dim not a multiple of the 128-lane tile (d=64 models, e.g.
      qwen2.5:0.5b) → q/k/v are ZERO-PADDED to 128 lanes at the kernel
      boundary and the output sliced back. Exact: padded dims contribute
      0 to every q·k dot and 0·p to the output; the kernel's internal
      1/sqrt(d_padded) scale is corrected by pre-scaling q.

    `logit_softcap` (gemma2's tanh capping, static) and `window`
    (sliding-window attention; 0 = full; may be a traced per-layer scalar)
    are handled INSIDE the kernels — windowed buckets also skip the key
    blocks below each q block's window.

    Under `mesh` (VERDICT r04 #2) the kernel runs inside a full-manual
    shard_map with heads split over tp — attention is embarrassingly
    parallel over kv-head groups, so each shard runs the kernel on its
    head slice with no collectives (ops/kvcache.py kernel_mesh_axis).
    """
    use, interpret = _pallas_mode(use_pallas)
    t, d = q.shape[1], q.shape[3]
    if not use or t % min(128, t) != 0:
        record_kernel_path("attention_prefill", False)
        return attention_prefill_ref(
            q, k, v, seq_lens, logit_softcap=logit_softcap, window=window
        )
    mode, ax = kernel_mesh_axis(mesh, k.shape[2], q.shape[2])
    if mode == "ref":
        record_kernel_path("attention_prefill", False)
        return attention_prefill_ref(
            q, k, v, seq_lens, logit_softcap=logit_softcap, window=window
        )
    record_kernel_path("attention_prefill", True)
    kernel = partial(
        _prefill_kernel, interpret=interpret, softcap=float(logit_softcap)
    )

    def _shadow(out):
        # numerics sanitizer (analysis/numcheck.py): padding rows are
        # unspecified kernel output — compare the valid region only, the
        # same contract the differential tests apply
        if not numcheck.active():
            return out
        return numcheck.shadow(
            "attention_prefill", out,
            lambda: attention_prefill_ref(
                q, k, v, seq_lens, logit_softcap=logit_softcap,
                window=window),
            valid=jnp.arange(q.shape[1])[None, :] < seq_lens[:, None],
        )

    if mode == "direct":
        return _shadow(kernel(q, k, v, seq_lens, window))
    from jax.sharding import PartitionSpec as P

    # window always travels as a scalar operand — the kernels read it from
    # SMEM at runtime either way, so there is nothing to specialize
    hs = P(None, None, ax, None)
    sm = _shard_map_kernel(
        mesh, kernel, in_specs=(hs, hs, hs, P(None), P()), out_specs=hs,
    )
    return _shadow(sm(q, k, v, seq_lens, jnp.asarray(window, jnp.int32)))


def _prefix_chunk_ref(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    table_row: jnp.ndarray,
    start: jnp.ndarray,
    total_len: jnp.ndarray,
    page_size: int,
    k_cur: jnp.ndarray | None = None,
    v_cur: jnp.ndarray | None = None,
    layer: jnp.ndarray | None = None,
    logit_softcap: float = 0.0,
    window: jnp.ndarray | int = 0,
) -> jnp.ndarray:
    """jnp reference for chunked-prefill attention: one chunk of queries
    against the slot's FULL cached context (prefix + this chunk), read
    from the page pool — ragged_paged_attention_ref's chunk region.

    q: [1, T, H, D] — chunk queries at absolute positions start + arange(T);
    k_pages/v_pages: [P, page_size, KVH, D] one layer's pool, or the full
    [L, P, ps, KVH, D] stack with `layer` selecting; table_row:
    [max_pages] the slot's pages; start: scalar absolute position of q[0];
    total_len: scalar = start + valid tokens in this chunk. Without
    k_cur/v_cur the chunk's K/V must already be in the pool; with them
    ([T, KVH, D], pool writes deferred to after the layer scan) the chunk
    rows are overlaid onto the gathered context at positions start+i.
    Returns [1, T, H, D]."""
    _, t, h, d = q.shape
    kvh = k_pages.shape[-2]
    g = h // kvh
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    if k_pages.ndim == 5:
        # full [L, P, ps, KVH, D] pool + layer index: gather exactly the
        # slot's pages from the selected layer (a combined advanced index —
        # never a whole-layer pool slice)
        li = jnp.int32(0) if layer is None else layer
        rows = jnp.maximum(table_row, 0)
        n = table_row.shape[0] * page_size
        if isinstance(k_pages, QuantPages):
            ks = k_pages.layer(li).take(rows).reshape(n, kvh, d)
            vs = v_pages.layer(li).take(rows).reshape(n, kvh, d)
        else:
            ks = k_pages[li, rows].reshape(n, kvh, d)
            vs = v_pages[li, rows].reshape(n, kvh, d)
    else:
        ks, vs = gather_kv(k_pages, v_pages, table_row, page_size)  # [N, KVH, D]
    if k_cur is not None:
        # overlay the fresh chunk at absolute positions [start, start+T):
        # pad by T rows so the dynamic_update_slice stays in bounds at the
        # capacity edge (start ≤ N; padded rows are sliced off again)
        pad = jnp.zeros((t, kvh, d), ks.dtype)
        n = ks.shape[0]
        ks = jax.lax.dynamic_update_slice(
            jnp.concatenate([ks, pad]), k_cur.astype(ks.dtype), (start, 0, 0)
        )[:n]
        vs = jax.lax.dynamic_update_slice(
            jnp.concatenate([vs, pad]), v_cur.astype(vs.dtype), (start, 0, 0)
        )[:n]
    qf = q.astype(jnp.float32).reshape(t, kvh, g, d)
    q_pos = start + jnp.arange(t)              # [T] absolute
    k_pos = jnp.arange(ks.shape[0])            # [N] absolute
    # causal over absolute positions covers both the prefix (k_pos < start
    # <= q_pos) and intra-chunk causality; total_len guards stale data in
    # owned-but-not-yet-valid page tails for padded q rows
    w = jnp.asarray(window, jnp.int32)
    dist = q_pos[:, None] - k_pos[None, :]
    mask = (
        (dist >= 0) & ((w <= 0) | (dist < w))
        & (k_pos[None, :] < total_len)
    )

    logits = jnp.einsum(
        "tkgd,nkd->kgtn", qf, ks.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ) * scale
    logits = _softcap(logits, logit_softcap)
    logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum(
        "kgtn,nkd->tkgd", probs, vs.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.reshape(1, t, h, d).astype(q.dtype)


def paged_attention_verify_ref(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    page_size: int,
    k_cur: jnp.ndarray,
    v_cur: jnp.ndarray,
    layer: jnp.ndarray | None = None,
    logit_softcap: float = 0.0,
    window: jnp.ndarray | int = 0,
    tree_pos: jnp.ndarray | None = None,
    tree_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Batched multi-token decode attention — the speculative-verify step:
    S slots × T candidate tokens each, attending the slot's paged prefix
    plus the candidates before them. vmap over slots of the dense
    per-slot gather + candidate overlay + causal mask — the same math as
    _prefix_chunk_ref with start = lengths[s] and every candidate row
    valid.

    q: [S, T, H, D] (candidate queries, post-rope); k_cur/v_cur:
    [S, T, KVH, D] (the candidates' fresh K/V, not yet in the pool);
    lengths: [S] cached-prefix length per slot — candidate i of slot s
    sits at absolute position lengths[s] + i. Pools may be one layer
    [P, ps, KVH, D] or the full [L, P, ps, KVH, D] stack with `layer` selecting (pass from
    inside a layer scan). Returns [S, T, H, D].

    Tree verify (ISSUE 18): with `tree_pos` ([T] i32 — node depths) and
    `tree_mask` ([T, T] bool — ancestor-or-self, row i marks node i's
    root-to-i path) the T candidates form a static-topology token TREE
    instead of a chain. Node i's K/V row is still stored/overlaid at
    absolute position lengths[s] + i, but its ROPE/logical position is
    lengths[s] + tree_pos[i]; node i's query attends the whole prefix
    plus exactly its tree ancestors (and itself), with the sliding
    window measured in LOGICAL distance. The topology is shared by all
    slots (a jit constant — the recompile tripwire stays green); per-slot
    raggedness lives in the accept walk, not the mask, because node
    validity is ancestor-closed so a live query never attends a dead
    node. A chain (tree_pos = arange(T), tree_mask = lower-triangular)
    produces the exact same mask as the chain branch, but the chain
    trace is kept on a separate branch so chain spec stays
    bit-identical."""
    s, t, h, d = q.shape
    tree = tree_pos is not None
    if tree:
        tree_pos = jnp.asarray(tree_pos, jnp.int32)
        tree_mask = jnp.asarray(tree_mask, bool)
    kvh = k_pages.shape[-2]
    g = h // kvh
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    w = jnp.asarray(window, jnp.int32)
    if k_pages.ndim == 5:
        li = jnp.int32(0) if layer is None else layer
        if isinstance(k_pages, QuantPages):
            k_pages, v_pages = k_pages.layer(li), v_pages.layer(li)
        else:
            k_pages = jax.lax.dynamic_index_in_dim(k_pages, li,
                                                   keepdims=False)
            v_pages = jax.lax.dynamic_index_in_dim(v_pages, li,
                                                   keepdims=False)

    def one_slot(qi, row, start, kc, vc):
        ks, vs = gather_kv(k_pages, v_pages, row, page_size)  # [N, KVH, D]
        # overlay the candidates at absolute positions [start, start+T):
        # pad by T rows so the update stays in bounds at the capacity
        # edge (padded rows are sliced off again; the out-of-capacity
        # case is a finished slot whose output is discarded)
        pad = jnp.zeros((t, kvh, ks.shape[-1]), ks.dtype)
        n = ks.shape[0]
        ks = jax.lax.dynamic_update_slice(
            jnp.concatenate([ks, pad]), kc.astype(ks.dtype), (start, 0, 0)
        )[:n]
        vs = jax.lax.dynamic_update_slice(
            jnp.concatenate([vs, pad]), vc.astype(vs.dtype), (start, 0, 0)
        )[:n]
        qf = qi.astype(jnp.float32).reshape(t, kvh, g, d)
        k_pos = jnp.arange(n)
        total = start + t
        if tree:
            # logical positions: query node i at start + depth[i]; a key
            # in the candidate region [start, start+T) is node j at
            # logical start + depth[j], a prefix key sits at its own
            # index. Candidate keys are valid iff ancestor-or-self;
            # prefix keys iff causal — both windowed on logical distance.
            q_pos = start + tree_pos
            is_cand = (k_pos >= start) & (k_pos < total)
            node = jnp.clip(k_pos - start, 0, t - 1)
            k_log = jnp.where(is_cand, start + tree_pos[node], k_pos)
            dist = q_pos[:, None] - k_log[None, :]
            mask = (
                jnp.where(is_cand[None, :], tree_mask[:, node], dist >= 0)
                & ((w <= 0) | (dist < w))
                & (k_pos[None, :] < total)
            )
        else:
            q_pos = start + jnp.arange(t)
            dist = q_pos[:, None] - k_pos[None, :]
            mask = (
                (dist >= 0) & ((w <= 0) | (dist < w))
                & (k_pos[None, :] < total)
            )
        logits = jnp.einsum(
            "tkgd,nkd->kgtn", qf, ks.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ) * scale
        logits = _softcap(logits, logit_softcap)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
        probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
        probs = probs / probs.sum(axis=-1, keepdims=True)
        out = jnp.einsum(
            "kgtn,nkd->tkgd", probs, vs.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        return out.reshape(t, h, d)

    out = jax.vmap(one_slot)(q, page_table, lengths, k_cur, v_cur)
    return out.astype(q.dtype)


def ragged_paged_attention(
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
    use_pallas: bool | None = None,
    logit_softcap: float = 0.0,
    window: jnp.ndarray | int = 0,
    mesh=None,
    tree_pos: jnp.ndarray | None = None,
    tree_mask: jnp.ndarray | None = None,
    latent_dv: int = 0,
) -> tuple[jnp.ndarray | None, jnp.ndarray | None]:
    """Ragged paged attention (the Ragged Paged Attention design): causal
    paged attention for a ragged token batch — one prefill CHUNK region
    plus S fixed-stride per-slot GROUPS — in a single kernel launch. The
    only dispatcher for the phases that read the page pool: chunked
    prefill passes a chunk region, decode a group region with Td = 1,
    verify a group region with Td = K+1, a mixed admission step both.

    Tree verify (ISSUE 18): `tree_pos` [Td] i32 + `tree_mask` [Td, Td]
    bool turn the GROUP region's Td tokens into a static-topology token
    tree (see paged_attention_verify_ref for the exact mask semantics).
    The topology is a jit constant shared by every slot; the kernel
    carries it as two scalar-prefetch rows (depths + ancestor BITMASKS,
    one int32 per node — hence Td <= 32 on the kernel path, larger
    budgets fall back to the jnp reference). Without the tree arguments
    the kernel is compiled with no tree operands at all.

    Regions (either may be absent; descriptors are per-sequence
    `(query_len, context_len, page_table_row)` in the RPA sense):

    - chunk: q_chunk [1, C, H, D] — one slot's prefill chunk at absolute
      positions chunk_start + i, prefix pages via chunk_row [max_pages],
      fresh K/V k_chunk/v_chunk [C, KVH, D] overlaid causally;
      chunk_total = chunk_start + valid rows (query_len = valid rows).
    - group: q_group [S, Td, H, D] — Td query tokens per slot (Td = 1 for
      decode, K+1 for spec-verify) at positions group_lengths[s] + i
      against page_table[s]; fresh K/V k_group/v_group [S, Td, KVH, D]
      merged in-register. Slots with length 0 (inactive) compute garbage
      cheaply — callers mask on `active`.

    Pools may be one layer [P, ps, KVH, D] or the full stack with `layer`
    selecting (pass from inside a layer scan). Returns (chunk_out,
    group_out), each shaped like its q (None when the region is absent).

    Latent pools (MLA's absorbed form, `latent_dv` > 0): `k_pages` holds
    one cache head whose row [latent, RoPE key] is the key, and the
    row's first `latent_dv` values are the value; `v_pages`, `v_chunk`
    and `v_group` are None, the queries are as wide as the row (the
    caller folds the key up-projection and its softmax scale into them:
    the scale applied here is rsqrt(row width)) and the outputs are
    `latent_dv` wide. The kernel reads each page once.

    Kernel path: ONE pallas_call with a static grid over query-token tiles
    (C/BQ chunk tiles + S group tiles, pallas_kernels.ragged_attention) —
    a mixed prefill+decode+verify engine step is a single launch. d=64
    models keep the kernel path WITHOUT the 2x lane-padded pool when the
    per-shard (KVH*D) % 128 == 0: pages are stored unpadded (tile-aligned
    flat rows for the DMA) and lane-padded in-register at load — the
    KV-bytes win /admin/memory itemizes. `logit_softcap` (static) and
    `window` (may be a traced per-layer scalar, gemma2 alternates) are
    handled inside the kernel — windowed tiles never DMA pages below the
    window. Under `mesh` the kernel runs in a full-manual shard_map with
    kv heads split over tp, no collectives. jnp path:
    ragged_paged_attention_ref, region by region.
    """
    some_q = q_chunk if q_chunk is not None else q_group
    d, dpool = some_q.shape[-1], k_pages.shape[-1]
    assert (latent_dv > 0) == (v_pages is None), "a latent pool has no V"
    if dpool != d:
        # lane-padded pool (KVH*D not lane-aligned): pad q/fresh-K/V at
        # the boundary and slice back — exact, see _lane_pad_qkv
        if q_chunk is not None:
            q_chunk, k_chunk, v_chunk = _lane_pad_qkv(
                q_chunk, k_chunk, v_chunk, dpool)
        if q_group is not None:
            q_group, k_group, v_group = _lane_pad_qkv(
                q_group, k_group, v_group, dpool)
        oc, og = ragged_paged_attention(
            k_pages, v_pages, page_size,
            q_chunk=q_chunk, chunk_row=chunk_row, chunk_start=chunk_start,
            chunk_total=chunk_total, k_chunk=k_chunk, v_chunk=v_chunk,
            q_group=q_group, page_table=page_table,
            group_lengths=group_lengths, k_group=k_group, v_group=v_group,
            layer=layer, use_pallas=use_pallas, logit_softcap=logit_softcap,
            window=window, mesh=mesh, tree_pos=tree_pos,
            tree_mask=tree_mask, latent_dv=latent_dv,
        )
        dout = latent_dv or d
        return (
            oc[..., :dout] if oc is not None else None,
            og[..., :dout] if og is not None else None,
        )

    h = some_q.shape[-2]
    kvh = k_pages.shape[-2]
    use, interpret = _pallas_mode(use_pallas)
    mode, ax = kernel_mesh_axis(mesh, kvh, h)
    # per-SHARD head count: under tp the kernel runs inside a shard_map
    # with kv heads split, so the VMEM gate must look at what one shard
    # actually sees
    kvh_local = kvh // mesh.shape["tp"] if ax == "tp" else kvh
    # Mosaic lane alignment: a head dim of whole 128-lane tiles (the
    # engine stores a narrower head lane-padded where kernels compile)
    lanes_ok = interpret or d % 128 == 0
    chunk_ok = True
    if q_chunk is not None:
        c = q_chunk.shape[1]
        # the chunk's fresh K/V stay VMEM-resident: budget gate, per
        # shard under tp
        chunk_ok = (
            c % min(128, c) == 0
            and 2 * c * kvh_local * d * q_chunk.dtype.itemsize
            <= _RAGGED_CHUNK_KV_CAP
        )
    quant = isinstance(k_pages, QuantPages)
    if latent_dv and (quant or mode == "wrap"):
        # a latent pool is one cache head: nothing to split over tp, and
        # no int8 form (the engine refuses both at construction)
        raise NotImplementedError("latent pool under a mesh or as int8")
    if quant and mode == "wrap":
        # int8 pools are single-device by engine policy (no shard_map
        # plumbing for the scale operands) — a meshed call is a wiring
        # bug upstream; serve the exact jnp path instead of guessing
        mode = "ref"
    has_tree = tree_pos is not None and q_group is not None
    tree_kw = {}
    if has_tree:
        if q_group.shape[1] > 32:
            # one int32 ancestor bitmask per node on the kernel path —
            # oversized budgets take the exact jnp reference instead
            mode = "ref"
        else:
            # topology is a host constant (static per process); pack the
            # ancestor rows into int32 bitmasks for the scalar-prefetch
            # lane of the kernel (bit j of row i = node j on node i's
            # root path)
            tm = np.asarray(tree_mask, bool)
            bits = np.zeros((tm.shape[0],), np.uint32)
            for j in range(tm.shape[1]):
                bits |= tm[:, j].astype(np.uint32) << np.uint32(j)
            tree_kw = {
                "tree_pos": jnp.asarray(np.asarray(tree_pos, np.int32),
                                        dtype=jnp.int32),
                "tree_bits": jnp.asarray(bits.view(np.int32),
                                         dtype=jnp.int32),
            }
    if use and mode != "ref" and lanes_ok and chunk_ok:
        from gridllm_tpu.ops import pallas_kernels

        record_kernel_path("attention_ragged", True)

        def _shadow(outs):
            # numerics sanitizer: shadow the whole launch against the
            # region-by-region jnp reference (QuantPages pools dequantize
            # through gather_kv/take inside the refs, so the int8 dequant
            # epilogue is compared against the jnp quant path)
            if not numcheck.active():
                return outs
            vc = vg = None
            if q_chunk is not None:
                vc = (jnp.arange(q_chunk.shape[1])[None, :]
                      < chunk_total - chunk_start)
            if q_group is not None:
                vg = group_lengths > 0
            return numcheck.shadow(
                "attention_ragged", outs,
                lambda: ragged_paged_attention_ref(
                    k_pages, v_pages, page_size,
                    q_chunk=q_chunk, chunk_row=chunk_row,
                    chunk_start=chunk_start, chunk_total=chunk_total,
                    k_chunk=k_chunk, v_chunk=v_chunk, q_group=q_group,
                    page_table=page_table, group_lengths=group_lengths,
                    k_group=k_group, v_group=v_group, layer=layer,
                    logit_softcap=logit_softcap, window=window,
                    tree_pos=tree_pos, tree_mask=tree_mask,
                    latent_dv=latent_dv),
                valid=(vc, vg),
            )

        if quant:
            # dequant epilogue (ISSUE 11): the kernel DMAs the int8 page
            # AND its [ps] scale row, multiplying after the load in the
            # flat-row read path — half the page HBM bytes per step
            kd, ksc = k_pages.data, k_pages.scale
            vd, vsc = v_pages.data, v_pages.scale
            if kd.ndim == 4:
                kd, vd = kd[None], vd[None]
                ksc, vsc = ksc[None], vsc[None]
            kernel = partial(
                pallas_kernels.ragged_attention, page_size=page_size,
                interpret=interpret, softcap=float(logit_softcap),
            )
            return _shadow(kernel(
                kd, vd,
                q_chunk=q_chunk, chunk_row=chunk_row,
                chunk_start=chunk_start, chunk_total=chunk_total,
                k_chunk=k_chunk, v_chunk=v_chunk,
                q_group=q_group, page_table=page_table,
                group_lengths=group_lengths, k_group=k_group,
                v_group=v_group, layer=layer, window=window,
                k_scale=ksc, v_scale=vsc, **tree_kw,
            ))
        kp = k_pages if k_pages.ndim == 5 else k_pages[None]
        vp = (None if latent_dv
              else v_pages if v_pages.ndim == 5 else v_pages[None])
        kernel = partial(
            pallas_kernels.ragged_attention, page_size=page_size,
            interpret=interpret, softcap=float(logit_softcap),
            latent_dv=latent_dv,
        )
        if mode == "direct":
            return _shadow(kernel(
                kp, vp,
                q_chunk=q_chunk, chunk_row=chunk_row,
                chunk_start=chunk_start, chunk_total=chunk_total,
                k_chunk=k_chunk, v_chunk=v_chunk,
                q_group=q_group, page_table=page_table,
                group_lengths=group_lengths, k_group=k_group,
                v_group=v_group, layer=layer, window=window, **tree_kw,
            ))
        from jax.sharding import PartitionSpec as P

        pool = P(None, None, None, ax, None)
        # dynamic operand assembly (shard_map bodies cannot close over
        # tracers): name → (value, spec); sorted for a stable order
        opt = {"window": (jnp.asarray(window, jnp.int32), P())}
        if layer is not None:
            opt["layer"] = (layer, P())
        if q_chunk is not None:
            opt["q_chunk"] = (q_chunk, P(None, None, ax, None))
            opt["chunk_row"] = (chunk_row, P(None))
            opt["chunk_start"] = (chunk_start, P())
            opt["chunk_total"] = (chunk_total, P())
            opt["k_chunk"] = (k_chunk, P(None, ax, None))
            opt["v_chunk"] = (v_chunk, P(None, ax, None))
        if q_group is not None:
            opt["q_group"] = (q_group, P(None, None, ax, None))
            opt["page_table"] = (page_table, P(None, None))
            opt["group_lengths"] = (group_lengths, P(None))
            opt["k_group"] = (k_group, P(None, None, ax, None))
            opt["v_group"] = (v_group, P(None, None, ax, None))
        for tn, tv in tree_kw.items():
            opt[tn] = (tv, P(None))
        names = sorted(opt)

        out_specs = (
            (P(None, None, ax, None),) if q_chunk is not None else ()
        ) + (
            (P(None, None, ax, None),) if q_group is not None else ()
        )

        def sm_tuple(kp, vp, *dyn):
            oc, og = kernel(kp, vp, **dict(zip(names, dyn)))
            return tuple(o for o in (oc, og) if o is not None)

        sm = _shard_map_kernel(
            mesh, sm_tuple,
            in_specs=(pool, pool, *(opt[n][1] for n in names)),
            out_specs=out_specs,
        )
        outs = sm(kp, vp, *(opt[n][0] for n in names))
        it = iter(outs)
        return _shadow((
            next(it) if q_chunk is not None else None,
            next(it) if q_group is not None else None,
        ))

    record_kernel_path("attention_ragged", False)
    return ragged_paged_attention_ref(
        k_pages, v_pages, page_size,
        q_chunk=q_chunk, chunk_row=chunk_row, chunk_start=chunk_start,
        chunk_total=chunk_total, k_chunk=k_chunk, v_chunk=v_chunk,
        q_group=q_group, page_table=page_table,
        group_lengths=group_lengths, k_group=k_group, v_group=v_group,
        layer=layer, logit_softcap=logit_softcap, window=window,
        tree_pos=tree_pos, tree_mask=tree_mask, latent_dv=latent_dv,
    )


def ragged_paged_attention_ref(
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
    logit_softcap: float = 0.0,
    window: jnp.ndarray | int = 0,
    tree_pos: jnp.ndarray | None = None,
    tree_mask: jnp.ndarray | None = None,
    latent_dv: int = 0,
) -> tuple[jnp.ndarray | None, jnp.ndarray | None]:
    """jnp reference for the ragged launch, composed from the per-region
    references: the chunk region is _prefix_chunk_ref, a Td = 1 group is
    paged_attention_decode_ref, a wider group is
    paged_attention_verify_ref (its tree branch when
    `tree_pos`/`tree_mask` are given). The fallback leg of
    ragged_paged_attention, and the oracle the KERNELS registry and the
    numerics sanitizer hold the ragged kernel to. A latent pool
    (`latent_dv`) is read as its own V: softmax(.) @ row, of which the
    first `latent_dv` columns are softmax(.) @ latent."""
    if latent_dv:
        oc, og = ragged_paged_attention_ref(
            k_pages, k_pages, page_size, q_chunk=q_chunk,
            chunk_row=chunk_row, chunk_start=chunk_start,
            chunk_total=chunk_total, k_chunk=k_chunk, v_chunk=k_chunk,
            q_group=q_group, page_table=page_table,
            group_lengths=group_lengths, k_group=k_group, v_group=k_group,
            layer=layer, logit_softcap=logit_softcap, window=window,
            tree_pos=tree_pos, tree_mask=tree_mask)
        return (None if oc is None else oc[..., :latent_dv],
                None if og is None else og[..., :latent_dv])
    out_chunk = out_group = None
    if q_chunk is not None:
        out_chunk = _prefix_chunk_ref(
            q_chunk, k_pages, v_pages, chunk_row, chunk_start, chunk_total,
            page_size, k_cur=k_chunk, v_cur=v_chunk, layer=layer,
            logit_softcap=logit_softcap, window=window,
        )
    if q_group is not None:
        td = q_group.shape[1]
        if tree_pos is not None:
            out_group = paged_attention_verify_ref(
                q_group, k_pages, v_pages, page_table, group_lengths,
                page_size, k_group, v_group, layer=layer,
                logit_softcap=logit_softcap, window=window,
                tree_pos=tree_pos, tree_mask=tree_mask,
            )
        elif td == 1:
            # Td == 1 is decode: its reference takes one layer's pool
            kp, vp = k_pages, v_pages
            if kp.ndim == 5:
                li = jnp.int32(0) if layer is None else layer
                if isinstance(kp, QuantPages):
                    kp, vp = kp.layer(li), vp.layer(li)
                else:
                    kp = jax.lax.dynamic_index_in_dim(kp, li,
                                                      keepdims=False)
                    vp = jax.lax.dynamic_index_in_dim(vp, li,
                                                      keepdims=False)
            out_group = paged_attention_decode_ref(
                q_group[:, 0], kp, vp, page_table, group_lengths, page_size,
                k_cur=k_group[:, 0], v_cur=v_group[:, 0],
                logit_softcap=logit_softcap, window=window,
            )[:, None]
        else:
            out_group = paged_attention_verify_ref(
                q_group, k_pages, v_pages, page_table, group_lengths,
                page_size, k_group, v_group, layer=layer,
                logit_softcap=logit_softcap, window=window,
            )
    return out_chunk, out_group


def _softcap(logits: jnp.ndarray, cap: float) -> jnp.ndarray:
    """gemma2's attn_logit_softcapping: cap * tanh(logits / cap), applied
    BEFORE masking (HF Gemma2Attention order)."""
    return cap * jnp.tanh(logits / cap) if cap else logits


def attention_prefill_ref(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    seq_lens: jnp.ndarray,
    logit_softcap: float = 0.0,
    window: jnp.ndarray | int = 0,
) -> jnp.ndarray:
    """Causal self-attention over one self-contained chunk (whole prompt).

    q: [B, T, H, D]; k/v: [B, T, KVH, D]; seq_lens: [B] valid tokens
    (padding keys masked out). Chunked prefill against an existing cached
    prefix is NOT handled here — that variant must read prefix K/V from the
    page pool and will land with the Pallas kernels. Returns [B, T, H, D].

    `logit_softcap`: tanh capping of attention logits (gemma2).
    `window`: sliding-window attention — a query attends keys at distance
    < window only (0 = full causal; may be a traced per-layer scalar).
    """
    b, t, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    qf = q.astype(jnp.float32).reshape(b, t, kvh, g, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # [B, KVH, G, Tq, Tk]
    logits = jnp.einsum("btkgd,bskd->bkgts", qf, kf, precision=jax.lax.Precision.HIGHEST) * scale
    logits = _softcap(logits, logit_softcap)

    q_pos = jnp.arange(t)[:, None]  # [Tq, 1]
    k_pos = jnp.arange(t)[None, :]  # [1, Tk]
    causal = q_pos >= k_pos
    w = jnp.asarray(window, jnp.int32)
    in_window = (w <= 0) | (q_pos - k_pos < w)
    valid = k_pos < seq_lens[:, None, None, None, None]
    mask = (causal & in_window)[None, None, None] & valid
    logits = jnp.where(mask, logits, _NEG_INF)

    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, vf, precision=jax.lax.Precision.HIGHEST)
    return out.reshape(b, t, h, d).astype(q.dtype)


def paged_attention_decode_ref(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    page_size: int,
    k_cur: jnp.ndarray | None = None,
    v_cur: jnp.ndarray | None = None,
    logit_softcap: float = 0.0,
    window: jnp.ndarray | int = 0,
) -> jnp.ndarray:
    """One-token-per-slot decode attention against the paged cache.

    q: [S, H, D] (the single new token per slot, post-rope);
    k_pages/v_pages: [P, page_size, KVH, D] (one layer's pool);
    page_table: [S, max_pages]. Without k_cur/v_cur, lengths: [S] valid
    tokens per slot *including* the current token (already written to the
    cache). With k_cur/v_cur ([S, KVH, D]), lengths counts the cached
    prefix only and the current token is overlaid at position lengths[s]
    before attending (the engine defers all pool writes to one all-layer
    kernel after the layer scan, so the pool lags one token during decode).
    Returns [S, H, D].

    `logit_softcap`/`window` as in attention_prefill_ref (the current
    token sits at position total-1; keys at distance >= window from it
    are masked).

    Reference implementation: materializes each slot's max context via
    gather. The Pallas kernel (ops/pallas_kernels.py) streams only valid
    pages instead.
    """
    s, h, d = q.shape
    kvh = k_pages.shape[2]
    g = h // kvh
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    merge_cur = k_cur is not None
    if not merge_cur:
        k_cur = jnp.zeros((s, kvh, d), k_pages.dtype)
        v_cur = jnp.zeros((s, kvh, d), v_pages.dtype)
    w = jnp.asarray(window, jnp.int32)

    def one_slot(qi, row, ln, kc, vc):
        ks, vs = gather_kv(k_pages, v_pages, row, page_size)  # [N, KVH, D]
        total = ln
        if merge_cur:
            # current token overlaid at index ln (clamped within capacity;
            # mode="drop" guards the full-capacity edge, where the caller
            # has already finished the slot)
            ks = ks.at[ln].set(kc, mode="drop")
            vs = vs.at[ln].set(vc, mode="drop")
            total = ln + 1
        qf = qi.astype(jnp.float32).reshape(kvh, g, d)
        logits = jnp.einsum("kgd,nkd->kgn", qf, ks.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST) * scale
        logits = _softcap(logits, logit_softcap)
        k_pos = jnp.arange(ks.shape[0])
        valid = k_pos < total
        valid &= (w <= 0) | ((total - 1) - k_pos < w)
        logits = jnp.where(valid[None, None, :], logits, _NEG_INF)
        probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
        probs = probs / probs.sum(axis=-1, keepdims=True)
        return jnp.einsum("kgn,nkd->kgd", probs, vs.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST).reshape(h, d)

    out = jax.vmap(one_slot)(q, page_table, lengths, k_cur, v_cur)
    return out.astype(q.dtype)
