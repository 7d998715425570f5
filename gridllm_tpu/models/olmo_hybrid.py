"""Olmo-Hybrid decoder (olmo-hybrid:7b, PR 42): gated delta-rule layers
3:1 with full attention.

Two kinds of layer in whole periods (`cfg.layer_period`: linear layers,
then one full layer), and two kinds of cache: a full layer's K and V go
to the page pool (whose leading axis is the FULL layers only: pool layer p
is period p's), a linear layer keeps a recurrent state a slot
(`PagedKVCache.rec`, ops/kvcache.RecurrentState; linear layer
`p * (period - 1) + j`). benchmark/reference/olmo_hybrid_f32.py states the
equations; ops/linear_attn.py holds the delta rule's two forms.

The block is the Olmo family's reordered one, no norm before a mixer:
`h = x + RMSNorm(mix(x))`, `out = h + RMSNorm(SwiGLU(h))`. A full layer
norms q and k over their whole width and has NO rotary embedding
(`rope_theta: null` as published: the recurrent layers carry position).

Params: `full` is one tree stacked [periods, ...]; `linear` is a tuple of
period - 1 such trees, one for each place in the period (a single tree
[periods, period - 1, ...] made the scan copy a period's 1.3 GB of weights
out of it at every step: PERF.md, PR 42). ONE `lax.scan` over periods
serves every phase, a phase being a pair of closures (`lin`, `att`) over
`_stack`.

The entry points are the ones an engine launches, and `validate_mesh`
refuses every mesh: `hidden_states` (/api/embed), `decode_step`,
`verify_step` and `mixed_step`, which admits every prompt chunk by chunk.
There is no `prefill` / `prefill_chunk`: only an `sp` or `pp` engine calls
those.

Phases and the state (see RecurrentState): a CHUNK launch (a prompt's
rows of one slot: the chunk region of `mixed_step`) starts from zeros at
position 0, else from the slot's own state (carried from the last chunk
launch, or a snapshot the engine restored), writes the state outright and
hands back the state at up to two page boundaries it passes (`state_io`:
the prefix cache's snapshots).
A STEP launch (`decode_step`, `verify_step`, the decode rows of
`mixed_step`) commits the slot's pending rows first and leaves its own
pending: `commit_verify` says how many of a verify launch's count.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from gridllm_tpu.models import llama, mixtral
from gridllm_tpu.models.configs import ModelConfig
from gridllm_tpu.ops import linear_attn as la
from gridllm_tpu.ops.attention import attention_prefill, ragged_paged_attention
from gridllm_tpu.ops.kvcache import (
    PagedKVCache,
    RecurrentState,
    write_decode_all,
    write_multi_all,
    write_prefill_all,
)
from gridllm_tpu.ops.layers import rms_norm

Params = dict[str, Any]
# page boundaries one chunk launch can hand the state back at
SAVES = 2


def validate_mesh(cfg: ModelConfig, mesh) -> None:
    """No mesh: the state's packed heads and the pending rows have no
    sharding written or proved. Refused rather than run unproved."""
    if mesh is not None:
        raise ValueError(
            f"{cfg.name}: olmo_hybrid is served on one device only (no "
            "sharding of the recurrent state has been written)")


def gdn_block(page_size: int) -> int:
    """Rows of one block of the chunked delta rule: 64, or what divides a
    page where pages are smaller (tests), so that every page boundary is
    a block's end."""
    return math.gcd(64, page_size)


def new_state(cfg: ModelConfig, slots: int, step_rows: int, snapshots: int,
              dtype=jnp.bfloat16) -> RecurrentState:
    return RecurrentState.create(
        cfg.linear_layers, slots, cfg.linear_num_heads,
        cfg.linear_key_head_dim, cfg.linear_value_head_dim,
        cfg.linear_conv_kernel, step_rows, snapshots, dtype,
        channel_decay=cfg.linear_channel_decay)


# ---------------------------------------------------------------------------
# the two mixers' row-wise parts
# ---------------------------------------------------------------------------


def _project(lp: Params, x: jnp.ndarray):
    """x [..., E] -> (the rows the convolution reads [..., C] (q, k, v side
    by side), the output gate [..., H*dv], the decay's and beta's
    pre-activations [..., H])."""
    p = llama._precision(x)
    pre = jnp.concatenate(
        [jnp.dot(x, lp[w], precision=p) for w in ("wq", "wk", "wv")], axis=-1)
    return (pre, jnp.dot(x, lp["wg"], precision=p),
            jnp.dot(x, lp["wa"], precision=p),
            jnp.dot(x, lp["wb"], precision=p))


def _qkvbg(cfg: ModelConfig, lp: Params, conv: jnp.ndarray, a, bb):
    """The convolution's output [..., C] (float32, SiLU applied) and the
    pre-activations -> q^, k^ [..., H, dk], v [..., H, dv], beta and the
    log decay [..., H], all float32. Under `cfg.linear_channel_decay` (Kimi
    Delta Attention) `a` and dt_bias are [..., H*dk] and the log decay a
    value a key channel, [..., H, dk]."""
    h, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    q, k, v = jnp.split(conv, [h * dk, 2 * h * dk], axis=-1)
    q = la.l2norm(q.reshape(*q.shape[:-1], h, dk)) * dk ** -0.5
    k = la.l2norm(k.reshape(*k.shape[:-1], h, dk))
    v = v.reshape(*v.shape[:-1], h, dv)
    b = jax.nn.sigmoid(bb.astype(jnp.float32)) * (
        2.0 if cfg.linear_allow_neg_eigval else 1.0)
    if cfg.linear_channel_decay:
        step = jax.nn.softplus(
            a.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
        g = -jnp.exp(lp["A_log"].astype(jnp.float32))[:, None] * (
            step.reshape(*step.shape[:-1], h, dk))
        return q, k, v, b, g
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
    return q, k, v, b, g


def _delta_ops(cfg: ModelConfig):
    """(chunk, step) of ops/linear_attn by the decay's shape."""
    if cfg.linear_channel_decay:
        return la.kda_chunk, la.kda_step
    return la.gdn_chunk, la.gdn_step


def _gated_out(cfg: ModelConfig, lp: Params, o: jnp.ndarray, gate, dtype,
               act=jax.nn.silu):
    """o [..., H, dv] float32 -> W_o [RMSNorm_dv(o) * act(gate)]."""
    with jax.named_scope("gdn_gate"):
        gate = gate.reshape(o.shape).astype(jnp.float32)
        y = rms_norm(o, lp["o_norm"], cfg.rms_eps) * act(gate)
        y = y.reshape(*y.shape[:-2], -1).astype(dtype)
    return jnp.dot(y, lp["wo"], precision=llama._precision(y))


def _full_qkv(cfg: ModelConfig, lp: Params, x: jnp.ndarray):
    """x [..., E] -> q, k, v [..., H, D]: q and k normed over the whole
    width (the family's QK-norm), no rotary embedding."""
    p = llama._precision(x)
    q = rms_norm(jnp.dot(x, lp["wq"], precision=p), lp["q_norm"], cfg.rms_eps)
    k = rms_norm(jnp.dot(x, lp["wk"], precision=p), lp["k_norm"], cfg.rms_eps)
    v = jnp.dot(x, lp["wv"], precision=p)
    shape = (*x.shape[:-1], cfg.num_heads, cfg.head_dim_)
    return q.reshape(shape), k.reshape(shape), v.reshape(shape)


def _pool_heads(cfg: ModelConfig, *qkv):
    """q, k, v [..., H, D] with zero heads behind the real ones up to the
    pool's head count (`cfg.cache_heads`: 30 heads are stored as 32)."""
    pad = cfg.cache_heads - cfg.num_heads
    if not pad:
        return qkv
    return tuple(jnp.pad(z, [(0, 0)] * (z.ndim - 2) + [(0, pad), (0, 0)])
                 for z in qkv)


def _attn_out(cfg: ModelConfig, lp: Params, o: jnp.ndarray, x):
    """The paged attention's output [..., pool heads, D] -> W_o of the
    real heads' [..., H * D]."""
    o = o[..., :cfg.num_heads, :]
    return jnp.dot(o.reshape(*o.shape[:-2], -1), lp["wo"],
                   precision=llama._precision(x))


def _block(cfg: ModelConfig, lp: Params, x, mixed):
    """The reordered block around a mixer's output."""
    h = x + rms_norm(mixed, lp["attn_norm"], cfg.rms_eps)
    return h + rms_norm(llama._mlp(lp, h), lp["mlp_norm"], cfg.rms_eps)


def _stack(params: Params, cfg: ModelConfig, x, carry, lin, att):
    """Every layer on x: ONE scan over periods, a period's linear layers
    then its full one. `lin(lp, li, x, carry) -> (mixed, carry, ys)` with
    li the linear layer's index, `att(lp, pi, x) -> (mixed, ys)` with pi
    the pool's layer. Returns (x, carry, the linear layers' ys stacked
    [periods, period - 1, ...], the full layers' ys [periods, ...])."""
    per = cfg.layer_period - 1
    n = cfg.num_layers // cfg.layer_period
    assert not cfg.layer_tail, "olmo_hybrid is whole periods only"

    def body(c, xs):
        x, carry = c
        lin_p, full_p, pi = xs
        lys = []
        for j, lp in enumerate(lin_p):
            mixed, carry, ly = lin(lp, pi * per + j, x, carry)
            x = _block(cfg, lp, x, mixed)
            lys.append(ly)
        mixed, fy = att(full_p, pi, x)
        x = _block(cfg, full_p, x, mixed)
        lys = jax.tree.map(lambda *a: jnp.stack(a), *lys) if (
            lys[0] is not None) else None
        return (x, carry), (lys, fy)

    (x, carry), (lys, fys) = jax.lax.scan(
        body, (x, carry),
        (params["linear"], params["full"], jnp.arange(n, dtype=jnp.int32)))
    return x, carry, lys, fys


# ---------------------------------------------------------------------------
# cache-free: forward, hidden_states
# ---------------------------------------------------------------------------


def _free_delta(cfg: ModelConfig, lp: Params, pre, a, bb, live):
    """The delta rule cache-free: `_project`'s rows of whole sequences
    [B, T, ...], live [B, T], each sequence from a zero state in the
    chunked form (jnp). Returns o [B, T, H, dv]."""
    t = pre.shape[1]
    block = 64
    pad = -t % block
    xfull = jnp.pad(pre, [(0, 0), (cfg.linear_conv_kernel - 1, 0), (0, 0)])
    q, k, v, bt, g = _qkvbg(cfg, lp, la.causal_conv(xfull, lp["conv_w"]),
                            a, bb)
    bt = jnp.where(live[..., None], bt, 0.0)
    g = jnp.where(live.reshape(live.shape + (1,) * (g.ndim - 2)), g, 0.0)
    q, k, v = (jnp.where(live[..., None, None], z, 0.0) for z in (q, k, v))

    def one(q, k, v, bt, g):
        rows = [jnp.pad(z, [(0, pad)] + [(0, 0)] * (z.ndim - 1))
                for z in (q, k, v, bt, g)]
        s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
        o, _, _ = la._chain(s0, la._wy(*rows, block),
                            jnp.zeros((0,), jnp.int32))
        return jnp.moveaxis(o, 1, 2).reshape(t + pad, *v.shape[1:])[:t]

    return jax.vmap(one)(q, k, v, bt, g)


def hidden_states(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  seq_lens: jnp.ndarray | None = None, mesh=None) -> jnp.ndarray:
    """Final-norm hidden states [B, T, E], cache-free: each sequence from
    a zero state in the chunked form (jnp), plain causal attention."""
    b, t = tokens.shape
    x = params["embed"][tokens]
    if seq_lens is None:
        seq_lens = jnp.full((b,), t, jnp.int32)
    live = jnp.arange(t)[None] < seq_lens[:, None]

    def lin(lp, li, x, carry):
        pre, gate, a, bb = _project(lp, x)
        o = _free_delta(cfg, lp, pre, a, bb, live)
        return _gated_out(cfg, lp, o, gate, x.dtype), carry, None

    def att(lp, pi, x):
        q, k, v = _full_qkv(cfg, lp, x)
        o = attention_prefill(q, k, v, seq_lens, use_pallas=cfg.use_pallas,
                              mesh=mesh)
        return jnp.dot(o.reshape(b, t, -1), lp["wo"],
                       precision=llama._precision(x)), None

    x, _, _, _ = _stack(params, cfg, x, None, lin, att)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            mesh=None) -> jnp.ndarray:
    """Cache-free full forward: tokens [B, T] -> logits [B, T, V] (fp32)."""
    return llama._unembed(cfg, params, hidden_states(params, cfg, tokens,
                                                     mesh=mesh))


# ---------------------------------------------------------------------------
# through the cache: a chunk region, a step region
# ---------------------------------------------------------------------------


def _at(arr, li, value, *idx):
    """arr[li, *idx] = value (in place on a scan's carry)."""
    return arr.at[(li, *idx)].set(value.astype(arr.dtype))


def _chunk_region(cfg: ModelConfig, lp: Params, li, rec: RecurrentState,
                  rows, slot, start, length, save_pos, block: int):
    """One linear layer on ONE slot's chunk rows (`rows` = _project's
    outputs for them, [c, ...]) at positions start + i, `length` of them
    live. Returns (o [c, H, dv], rec with the slot's state and convolution
    tail after the chunk and nothing pending, (states, tails) at
    `save_pos` [SAVES] (absolute positions; those the chunk does not pass
    read zeros))."""
    pre, a, bb = rows
    c = pre.shape[0]
    taps = cfg.linear_conv_kernel - 1
    fresh = start == 0
    tail0 = jnp.where(fresh, 0, rec.conv[li, slot].reshape(taps, -1))
    xfull = jnp.concatenate([tail0, pre])                    # [taps + c, C]
    q, k, v, b, g = _qkvbg(cfg, lp, la.causal_conv(xfull, lp["conv_w"]), a, bb)
    # rows that hold no token are ZEROS to the delta rule (beta = g = 0
    # alone would leave 0 x NaN in a block's system where an earlier
    # layer's kernel left a padded row unwritten: PERF.md, PR 42)
    live = (jnp.arange(c) < length)[:, None]
    q, k, v = (jnp.where(live[..., None], z, 0.0) for z in (q, k, v))
    b = jnp.where(live, b, 0.0)
    g = jnp.where(live.reshape(live.shape + (1,) * (g.ndim - 2)), g, 0.0)
    s0 = jnp.where(fresh, 0.0, rec.state[li, slot])
    passed = (save_pos > start) & (save_pos <= start + length)
    rel = jnp.where(passed, save_pos - start, 0)
    keep = jnp.where(passed, rel // block - 1, -1)
    o, s1, kept = _delta_ops(cfg)[0](s0, q, k, v, b, g, keep, block,
                                     use_pallas=cfg.use_pallas)

    def tail_at(r):           # the rows before position start + r
        return jax.lax.dynamic_slice_in_dim(xfull, r, taps)

    rec = dataclasses.replace(
        rec, state=_at(rec.state, li, s1, slot),
        conv=_at(rec.conv, li, tail_at(length).reshape(-1), slot))
    return o, rec, (kept, jax.vmap(tail_at)(rel).reshape(rel.shape[0], -1))


def _step_region(cfg: ModelConfig, lp: Params, li, rec: RecurrentState,
                 rows, active):
    """One linear layer on t rows of EVERY slot (`rows` [S, t, ...]):
    commits each live slot's pending rows (state and convolution tail),
    runs the new rows on top, leaves them pending. Returns (o [S, t, H,
    dv], rec)."""
    # a slot that is not live runs rows of zeros: what it leaves pending
    # is then finite whatever an earlier layer's kernels left in its rows
    pre, a, bb = (jnp.where(active[:, None, None], z, 0) for z in rows)
    t = pre.shape[1]
    taps = cfg.linear_conv_kernel - 1
    assert t <= rec.step_rows, "more rows than the state's pending holds"
    s, ch = pre.shape[0], pre.shape[-1]
    n = jnp.where(active, rec.pend_n, 0)
    # the tail after the n rows that count: rows n .. n + taps of
    # [tail, pending rows]
    seen = jnp.concatenate([rec.conv[li], rec.pend_x[li]],
                           axis=1).reshape(s, -1, ch)
    tail = jax.vmap(lambda z, i: jax.lax.dynamic_slice_in_dim(z, i, taps))(
        seen, n)
    xfull = jnp.concatenate([tail, pre], axis=1)
    q, k, v, b, g = _qkvbg(cfg, lp, la.causal_conv(xfull, lp["conv_w"]), a, bb)
    pend = tuple(z[li] for z in (rec.pend_k, rec.pend_v, rec.pend_b,
                                 rec.pend_g))
    state, o = _delta_ops(cfg)[1](rec.state, li, pend, n, q, k, v, b, g,
                                  active, use_pallas=cfg.use_pallas)
    on = active[:, None, None]
    rows_ = slice(0, t)
    rec = dataclasses.replace(
        rec, state=state,
        conv=_at(rec.conv, li, jnp.where(
            on[:, 0], tail.reshape(s, -1), rec.conv[li])),
        pend_x=_at(rec.pend_x, li, pre.reshape(s, -1), slice(None),
                   slice(0, t * ch)),
        pend_k=_at(rec.pend_k, li, k, slice(None), rows_),
        pend_v=_at(rec.pend_v, li, v, slice(None), rows_),
        pend_b=_at(rec.pend_b, li, b, slice(None), rows_),
        pend_g=_at(rec.pend_g, li, g, slice(None), rows_))
    return o, rec


def _save_snapshots(rec: RecurrentState, saved, save_idx) -> RecurrentState:
    """The states a chunk launch handed back (`saved`: per linear layer
    [periods, period - 1, SAVES, ...]) into snapshot entries `save_idx`
    [SAVES] (-1: dropped)."""
    states, tails = (z.reshape(-1, *z.shape[2:]) for z in saved)
    at = jnp.where(save_idx >= 0, save_idx, rec.snap_state.shape[1])
    return dataclasses.replace(
        rec,
        snap_state=rec.snap_state.at[:, at].set(states, mode="drop"),
        snap_conv=rec.snap_conv.at[:, at].set(
            tails.astype(rec.snap_conv.dtype), mode="drop"))


def mixed_step(params: Params, cfg: ModelConfig, chunk_tokens, chunk_start,
               chunk_len, slot, table_row, tokens, cache, active, mesh=None,
               embeds=None, state_io=None):
    """One fused chunked-prefill + decode step (llama.mixed_step's
    contract): rows [0, C) the admitting slot's chunk against its cached
    prefix and its carried state, rows [C, C + S) one decode token a slot.
    `state_io` = (positions [SAVES], snapshot entries [SAVES]): page
    boundaries this chunk passes at which the state is saved."""
    del mesh
    c = chunk_tokens.shape[0]
    block = gdn_block(cache.page_size)
    assert c % block == 0, f"a chunk of {c} rows is not whole blocks of {block}"
    none = jnp.full((SAVES,), -1, jnp.int32)
    save_pos, save_idx = state_io if state_io is not None else (none, none)
    dt = params["embed"].dtype
    xc = params["embed"][chunk_tokens] if embeds is None else embeds
    x = jnp.concatenate([xc.astype(dt), params["embed"][tokens]])[None]
    total = chunk_start + chunk_len
    positions = cache.lengths

    def lin(lp, li, x, rec):
        pre, gate, a, bb = _project(lp, x[0])
        o, rec, saved = _chunk_region(
            cfg, lp, li, rec, (pre[:c], a[:c], bb[:c]), slot, chunk_start,
            chunk_len, save_pos, block)
        og, rec = _step_region(
            cfg, lp, li, rec, (pre[c:, None], a[c:, None], bb[c:, None]),
            active)
        o = jnp.concatenate([o, og[:, 0]])
        return _gated_out(cfg, lp, o, gate, x.dtype)[None], rec, saved

    def att(lp, pi, x):
        q, k, v = _pool_heads(cfg, *_full_qkv(cfg, lp, x[0]))
        oc, og = ragged_paged_attention(
            cache.k, cache.v, cache.page_size, layer=pi,
            use_pallas=cfg.use_pallas,
            q_chunk=q[None, :c], chunk_row=table_row, chunk_start=chunk_start,
            chunk_total=total, k_chunk=k[:c], v_chunk=v[:c],
            q_group=q[c:, None], page_table=cache.page_table,
            group_lengths=positions, k_group=k[c:, None], v_group=v[c:, None])
        o = jnp.concatenate([oc[0], og[:, 0]])
        return _attn_out(cfg, lp, o, x)[None], (k, v)

    x, rec, saved, (k_new, v_new) = _stack(params, cfg, x, cache.rec, lin, att)
    rec = _save_snapshots(rec, saved, save_idx)
    x = rms_norm(x[0], params["final_norm"], cfg.rms_eps)
    chunk_logits = llama._unembed(cfg, params, x[jnp.maximum(chunk_len - 1, 0)])
    dec_logits = llama._unembed(cfg, params, x[c:])
    k_pool, v_pool = write_prefill_all(
        cache.k, cache.v, k_new[:, :c], v_new[:, :c], table_row, chunk_start,
        chunk_len, cache.page_size, use_pallas=cfg.use_pallas)
    k_pool, v_pool = write_decode_all(
        k_pool, v_pool, k_new[:, c:], v_new[:, c:], cache.page_table,
        positions, active, cache.page_size, use_pallas=cfg.use_pallas)
    rec = dataclasses.replace(
        rec, pend_n=active.astype(jnp.int32).at[slot].set(0))
    new_lengths = jnp.minimum(
        cache.lengths + active.astype(jnp.int32), cache.max_context
    ).at[slot].set(total)
    return chunk_logits, dec_logits, dataclasses.replace(
        cache, k=k_pool, v=v_pool, rec=rec,
        page_table=cache.page_table.at[slot].set(table_row),
        lengths=new_lengths)


def _step_launch(params: Params, cfg: ModelConfig, tokens, cache, active):
    """t rows of every slot (decode: 1, verify: K + 1) at positions
    lengths + i. Returns (final-norm x [S, t, E], the full layers' K and V
    [periods, S, t, H, D], rec with the rows pending)."""
    s, t = tokens.shape
    x = params["embed"][tokens]
    base = cache.lengths

    def lin(lp, li, x, rec):
        pre, gate, a, bb = _project(lp, x)
        o, rec = _step_region(cfg, lp, li, rec, (pre, a, bb), active)
        return _gated_out(cfg, lp, o, gate, x.dtype), rec, None

    def att(lp, pi, x):
        q, k, v = _pool_heads(cfg, *_full_qkv(cfg, lp, x))
        _, o = ragged_paged_attention(
            cache.k, cache.v, cache.page_size, layer=pi,
            use_pallas=cfg.use_pallas, q_group=q,
            page_table=cache.page_table, group_lengths=base, k_group=k,
            v_group=v)
        return _attn_out(cfg, lp, o, x), (k, v)

    x, rec, _, (k_new, v_new) = _stack(params, cfg, x, cache.rec, lin, att)
    return rms_norm(x, params["final_norm"], cfg.rms_eps), k_new, v_new, rec


def decode_step(params: Params, cfg: ModelConfig, tokens, cache, active,
                mesh=None):
    """One decode step for ALL slots (llama.decode_step's contract)."""
    del mesh
    positions = cache.lengths
    x, k_new, v_new, rec = _step_launch(params, cfg, tokens[:, None], cache,
                                        active)
    logits = llama._unembed(cfg, params, x[:, 0])
    k_pool, v_pool = write_decode_all(
        cache.k, cache.v, k_new[:, :, 0], v_new[:, :, 0], cache.page_table,
        positions, active, cache.page_size, use_pallas=cfg.use_pallas)
    return logits, dataclasses.replace(
        cache, k=k_pool, v=v_pool,
        rec=dataclasses.replace(rec, pend_n=active.astype(jnp.int32)),
        lengths=jnp.minimum(cache.lengths + active.astype(jnp.int32),
                            cache.max_context))


def verify_step(params: Params, cfg: ModelConfig, tokens, cache, active,
                mesh=None, tree_pos=None, tree_mask=None):
    """One speculative-verify forward for ALL slots (llama.verify_step's
    contract: candidates written optimistically, lengths unchanged). The
    state is left with all K + 1 rows pending: `commit_verify` sets how
    many count, as `rollback_to_length` does for the pages."""
    del mesh
    if tree_pos is not None or tree_mask is not None:
        raise NotImplementedError(
            f"{cfg.name}: tree verification is not served for a recurrent "
            "state (a state has one past, not a tree of them)")
    s, t = tokens.shape
    x, k_new, v_new, rec = _step_launch(params, cfg, tokens, cache, active)
    logits = llama._unembed(cfg, params, x)
    positions = cache.lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    k_pool, v_pool = write_multi_all(
        cache.k, cache.v, k_new, v_new, cache.page_table, positions, active,
        cache.page_size, use_pallas=cfg.use_pallas)
    rec = dataclasses.replace(rec, pend_n=jnp.where(active, t, 0))
    return logits, dataclasses.replace(cache, k=k_pool, v=v_pool, rec=rec)


def commit_verify(cache: PagedKVCache, n_emit: jnp.ndarray,
                  active: jnp.ndarray) -> PagedKVCache:
    """Speculation's commit for the state: of the verify launch's pending
    rows the first `n_emit` [S] count; the next launch commits exactly
    those. No state moves here."""
    n = jnp.where(active, n_emit, 0).astype(jnp.int32)
    return dataclasses.replace(
        cache, rec=dataclasses.replace(cache.rec, pend_n=n))


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


@jax.jit
def _decay_leaves(key, proto):
    """A_log and dt_bias as the delta rule's published initialisation
    draws them: A uniform in (0, 16), the step dt log-uniform in (0.001,
    0.1) through softplus's inverse. One jit, like every random leaf."""
    ka, kd = jax.random.split(key)
    a = jax.random.uniform(ka, proto.shape, jnp.float32, 1e-3, 16.0)
    dt = jnp.exp(jax.random.uniform(
        kd, proto.shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
    return (jnp.log(a).astype(proto.dtype),
            (dt + jnp.log(-jnp.expm1(-dt))).astype(proto.dtype))


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params (tests, the benchmark's seeded weights)."""
    e, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    hd = cfg.num_heads * cfg.head_dim_
    per = cfg.layer_period - 1
    n = cfg.num_layers // cfg.layer_period
    ks = iter(jax.random.split(key, 64))

    def w(*shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5)
        return mixtral._normal_leaf(
            next(ks), shape=shape, scale=scale, dtype=dtype)

    def mlp():
        return {
            "attn_norm": jnp.ones((n, e), dtype),
            "mlp_norm": jnp.ones((n, e), dtype),
            "w_gate": w(n, e, f), "w_up": w(n, e, f), "w_down": w(n, f, e),
        }

    def linear():
        a_log, dt_bias = _decay_leaves(next(ks), jnp.zeros((n, h), dtype))
        return {
            **mlp(),
            "wq": w(n, e, h * dk), "wk": w(n, e, h * dk),
            "wv": w(n, e, h * dv), "wg": w(n, e, h * dv),
            "wa": w(n, e, h), "wb": w(n, e, h), "wo": w(n, h * dv, e),
            "conv_w": w(n, cfg.linear_conv_kernel, cfg.conv_channels,
                        scale=cfg.linear_conv_kernel ** -0.5),
            "A_log": a_log, "dt_bias": dt_bias,
            "o_norm": jnp.ones((n, dv), dtype),
        }

    params: Params = {
        "embed": w(v, e, scale=0.02),
        "linear": tuple(linear() for _ in range(per)),
        "full": {
            **mlp(),
            "wq": w(n, e, hd), "wk": w(n, e, hd), "wv": w(n, e, hd),
            "wo": w(n, hd, e),
            "q_norm": jnp.ones((n, hd), dtype),
            "k_norm": jnp.ones((n, hd), dtype),
        },
        "final_norm": jnp.ones((e,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = w(e, v, scale=0.02)
    return params


# ---------------------------------------------------------------------------
# HF weight conversion (layout contract with OlmoHybridForCausalLM; the
# tensor names are the flash-linear-attention GatedDeltaNet's and the Olmo
# family's, ASSUMED: the modeling file is not here)
# ---------------------------------------------------------------------------

_L = "model.layers.{}."
_MLP_MAP: dict[str, tuple[str, bool]] = {
    "attn_norm": (_L + "post_attention_layernorm.weight", False),
    "mlp_norm": (_L + "post_feedforward_layernorm.weight", False),
    "w_gate": (_L + "mlp.gate_proj.weight", True),
    "w_up": (_L + "mlp.up_proj.weight", True),
    "w_down": (_L + "mlp.down_proj.weight", True),
}
LINEAR_HF_MAP = {
    **_MLP_MAP,
    "wq": (_L + "linear_attn.q_proj.weight", True),
    "wk": (_L + "linear_attn.k_proj.weight", True),
    "wv": (_L + "linear_attn.v_proj.weight", True),
    "wg": (_L + "linear_attn.g_proj.weight", True),
    "wa": (_L + "linear_attn.a_proj.weight", True),
    "wb": (_L + "linear_attn.b_proj.weight", True),
    "wo": (_L + "linear_attn.o_proj.weight", True),
    "A_log": (_L + "linear_attn.A_log", False),
    "dt_bias": (_L + "linear_attn.dt_bias", False),
    "o_norm": (_L + "linear_attn.o_norm.weight", False),
}
FULL_HF_MAP = {
    **_MLP_MAP,
    "wq": (_L + "self_attn.q_proj.weight", True),
    "wk": (_L + "self_attn.k_proj.weight", True),
    "wv": (_L + "self_attn.v_proj.weight", True),
    "wo": (_L + "self_attn.o_proj.weight", True),
    "q_norm": (_L + "self_attn.q_norm.weight", False),
    "k_norm": (_L + "self_attn.k_norm.weight", False),
}
# the three depthwise convolutions [channels, 1, K], side by side in conv_w
_CONVS = tuple(_L + f"linear_attn.{n}_conv1d.weight" for n in "qkv")


def from_getter(cfg: ModelConfig, get, dtype=jnp.bfloat16, place=None) -> Params:
    """The pytree from `get(published tensor name) -> host array`: a
    stacked tree for each place in the period."""
    import numpy as np

    if place is None:
        def place(path, arr):
            return jnp.asarray(arr, dtype)

    p = cfg.layer_period
    n = cfg.num_layers // p

    def leaf(tmpl, tr, i):
        a = np.asarray(get(tmpl.format(i)))
        return a.T if tr else a

    def tree(name, name_map, at):
        """One stacked tree: the layers at place `at` of every period."""
        return {key: place((name, key), np.stack(
            [leaf(tmpl, tr, pi * p + at) for pi in range(n)]))
            for key, (tmpl, tr) in name_map.items()}

    def linear(j):
        out = tree(f"linear{j}", LINEAR_HF_MAP, j)
        out["conv_w"] = place((f"linear{j}", "conv_w"), np.stack([
            np.concatenate([np.asarray(get(c.format(pi * p + j)))[:, 0, :].T
                            for c in _CONVS], axis=-1) for pi in range(n)]))
        return out

    params: Params = {
        "embed": place(("embed",), np.asarray(get("model.embed_tokens.weight"))),
        "linear": tuple(linear(j) for j in range(p - 1)),
        "full": tree("full", FULL_HF_MAP, p - 1),
        "final_norm": place(("final_norm",), np.asarray(get("model.norm.weight"))),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = place(
            ("lm_head",), np.asarray(get("lm_head.weight")).T)
    return params
