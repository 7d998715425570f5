"""Granite 4.0-H decoder (granite4:h-micro, PR 61): Mamba-2 state-space
layers 9:1 with attention without positions, the family's four
multipliers, a shared SwiGLU and no routed expert.

Two kinds of layer in whole periods (`_period`: the published pattern is
four periods of ten, `m m m m m A m m m m`: the attention layer INSIDE the
period, not at its end), and two kinds of cache as in olmo_hybrid: an
attention layer's K and V go to the page pool (pool layer p is period
p's), a Mamba-2 layer keeps a recurrent state a slot (`PagedKVCache.rec`,
ops/kvcache.RecurrentState with keys a GROUP: state-space layer `p * (period
- 1) + j`). benchmark/reference/granite_hybrid_f32.py states the
equations; ops/linear_attn.py holds the scan's two forms (`ssd_chunk`,
`ssd_step`: the delta rule's block update without the delta, B and C
shared by every head).

The block is pre-norm with the residual multiplier on both branches:
`h = x + rm * mix(RMSNorm(x))`, `out = h + rm * MLP(RMSNorm(h))`; the
embedding is multiplied by `embedding_multiplier`, the logits divided by
`logits_scaling`, attention scores scaled by `attention_multiplier` (not
head_dim^-0.5: folded into q, the kernels apply head_dim^-0.5).

A Mamba-2 layer: `[z, xBC, dt] = W_in u`; `xBC <- silu(conv(xBC) + b)`
(depthwise causal, the last taps a slot's `conv` rows); `[x, B, C] = xBC`;
`dt <- softplus(dt + dt_bias)`; per head `S <- exp(-exp(A_log) dt) S +
(dt x) B^T`, `y = S C + D x`; `y <- RMSNorm(y * silu(z))` over the whole
inner width (gate BEFORE the norm); `W_out y`.

Params: `attn` is one tree stacked [periods, ...]; `mamba` a tuple of
period - 1 such trees, one for each state-space place in the period (as
olmo_hybrid's `linear`, for the reason given there). ONE `lax.scan` over
periods serves every phase, a phase being a pair of closures (`lin`,
`att`) over `_stack`.

The entry points are the ones an engine launches, and `validate_mesh`
refuses every mesh: `hidden_states` (/api/embed), `decode_step`,
`verify_step`, `mixed_step` (every prompt, chunk by chunk) and
`commit_verify`. There is no `prefill` / `prefill_chunk`. Phases and the
state are olmo_hybrid's (see its docstring and RecurrentState).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from gridllm_tpu.models import llama, mixtral
from gridllm_tpu.models.configs import ModelConfig
from gridllm_tpu.models.olmo_hybrid import (  # noqa: F401  (engine hooks)
    SAVES,
    _at,
    _save_snapshots,
    commit_verify,
    gdn_block as ssd_block,
)
from gridllm_tpu.ops import linear_attn as la
from gridllm_tpu.ops.attention import attention_prefill, ragged_paged_attention
from gridllm_tpu.ops.kvcache import (
    RecurrentState,
    write_decode_all,
    write_multi_all,
    write_prefill_all,
)
from gridllm_tpu.ops.layers import rms_norm

Params = dict[str, Any]


def validate_mesh(cfg: ModelConfig, mesh) -> None:
    """No mesh: the state's packed heads, the shared B and C and the
    pending rows have no sharding written or proved."""
    if mesh is not None:
        raise ValueError(
            f"{cfg.name}: granite_hybrid is served on one device only (no "
            "sharding of the recurrent state has been written)")


def _period(cfg: ModelConfig) -> tuple[int, int]:
    """(length of the mixers' repeating pattern, the attention layer's
    place in it): whole periods with ONE attention layer each, wherever in
    the period it stands. Anything else is refused."""
    kinds = cfg.layer_types
    p = next((p for p in range(1, len(kinds) + 1)
              if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p)
              and kinds[:p].count("full_attention") == 1), 0)
    if not p or cfg.linear_groups != 1:
        raise ValueError(
            f"{cfg.name}: layer_types is not whole periods of state-space "
            f"layers around one attention layer, or B and C are not one "
            f"group: {kinds}, groups {cfg.linear_groups}")
    return p, kinds.index("full_attention")


def new_state(cfg: ModelConfig, slots: int, step_rows: int, snapshots: int,
              dtype=jnp.bfloat16) -> RecurrentState:
    return RecurrentState.create(
        cfg.linear_layers, slots, cfg.linear_num_heads,
        cfg.linear_key_head_dim, cfg.linear_value_head_dim,
        cfg.linear_conv_kernel, step_rows, snapshots, dtype,
        groups=cfg.linear_groups)


# ---------------------------------------------------------------------------
# the two mixers' row-wise parts
# ---------------------------------------------------------------------------


def _project(cfg: ModelConfig, lp: Params, u: jnp.ndarray):
    """u [..., E] (normed) -> (the gate z [..., H*dv], the rows the
    convolution reads xBC [..., C], dt's pre-activation [..., H])."""
    di = cfg.linear_num_heads * cfg.linear_value_head_dim
    zxd = jnp.dot(u, lp["w_in"], precision=llama._precision(u))
    return (zxd[..., :di], zxd[..., di:di + cfg.conv_channels],
            zxd[..., di + cfg.conv_channels:])


def _scan_rows(cfg: ModelConfig, lp: Params, xfull, dt_raw):
    """The rows before the convolution [..., K-1+T, C] (the tail, then
    the rows) and dt's pre-activation [..., T, H] -> x [..., T, H, dv], C
    and B [..., T, dk] (the scan's q and k), v = dt x [..., T, H, dv] and
    the log decay g [..., T, H], all float32."""
    h, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    with jax.named_scope("ssm_conv"):
        conv = la.causal_conv(xfull, lp["conv_w"], lp["conv_b"])
    x, b, c = jnp.split(conv, [h * dv, h * dv + dk], axis=-1)
    x = x.reshape(*x.shape[:-1], h, dv)
    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * dt
    return x, c, b, dt[..., None] * x, g


def _skip(lp: Params, o, x):
    """The scan's output plus the skip D x (a value a head)."""
    return o + lp["D"].astype(jnp.float32)[:, None] * x


def _gated_out(cfg: ModelConfig, lp: Params, y: jnp.ndarray, z, dtype):
    """y [..., H, dv] float32 -> W_out RMSNorm(y * silu(z)) over the WHOLE
    inner width: the gate before the norm."""
    with jax.named_scope("ssm_gate"):
        y = y.reshape(*y.shape[:-2], -1) * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm(y, lp["o_norm"], cfg.rms_eps).astype(dtype)
    return jnp.dot(y, lp["wo"], precision=llama._precision(y))


def _qkv(cfg: ModelConfig, lp: Params, u: jnp.ndarray):
    """u [..., E] -> q [..., H, D] (times attention_multiplier x sqrt(D):
    the attention kernels scale by D^-0.5), k, v [..., KVH, D]; no rotary
    embedding."""
    p = llama._precision(u)
    d = cfg.head_dim_
    fold = cfg.attention_multiplier * d ** 0.5
    q = jnp.dot(u, lp["wq"], precision=p) * jnp.asarray(fold, u.dtype)
    k, v = (jnp.dot(u, lp[w], precision=p) for w in ("wk", "wv"))
    return (q.reshape(*u.shape[:-1], cfg.num_heads, d),
            k.reshape(*u.shape[:-1], cfg.num_kv_heads, d),
            v.reshape(*u.shape[:-1], cfg.num_kv_heads, d))


def _attn_out(lp: Params, o: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(o.reshape(*o.shape[:-2], -1), lp["wo"],
                   precision=llama._precision(o))


def _embed(cfg: ModelConfig, params: Params, tokens) -> jnp.ndarray:
    x = params["embed"][tokens]
    return x * jnp.asarray(cfg.embedding_multiplier, x.dtype)


def _unembed(cfg: ModelConfig, params: Params, x) -> jnp.ndarray:
    return llama._unembed(cfg, params, x) / cfg.logits_scaling


def _stack(params: Params, cfg: ModelConfig, x, carry, lin, att):
    """Every layer on x: ONE scan over periods, a period's layers in their
    published order. `lin(lp, li, u, carry) -> (mixed, carry, ys)` with li
    the state-space layer's index and u the normed input, `att(lp, pi, u)
    -> (mixed, ys)` with pi the pool's layer. Returns (x, carry, the
    state-space layers' ys stacked [periods, period - 1, ...], the
    attention layers' ys [periods, ...])."""
    period, at = _period(cfg)
    per = period - 1
    rm = cfg.residual_multiplier

    def normed(lp, x):
        return rms_norm(x, lp["attn_norm"], cfg.rms_eps)

    def block(lp, x, mixed):
        h = x + rm * mixed
        return h + rm * llama._mlp(lp, rms_norm(h, lp["mlp_norm"], cfg.rms_eps))

    def body(c, xs):
        x, carry = c
        lin_p, att_p, pi = xs
        lys, fy = [], None
        for place in range(period):
            if place == at:
                mixed, fy = att(att_p, pi, normed(att_p, x))
                x = block(att_p, x, mixed)
                continue
            j = place - (place > at)
            lp = lin_p[j]
            mixed, carry, ly = lin(lp, pi * per + j, normed(lp, x), carry)
            x = block(lp, x, mixed)
            lys.append(ly)
        lys = jax.tree.map(lambda *a: jnp.stack(a), *lys) if (
            lys[0] is not None) else None
        return (x, carry), (lys, fy)

    (x, carry), (lys, fys) = jax.lax.scan(
        body, (x, carry),
        (params["mamba"], params["attn"],
         jnp.arange(cfg.num_layers // period, dtype=jnp.int32)))
    return x, carry, lys, fys


# ---------------------------------------------------------------------------
# cache-free: forward, hidden_states
# ---------------------------------------------------------------------------


def _free_scan(cfg: ModelConfig, lp: Params, pre, dt_raw, live):
    """The scan cache-free: `_project`'s rows of whole sequences [B, T,
    ...], live [B, T], each sequence from a zero state in the chunked form
    (jnp). Returns y [B, T, H, dv] (the skip added)."""
    t = pre.shape[1]
    block = 64
    pad = -t % block
    xfull = jnp.pad(pre, [(0, 0), (cfg.linear_conv_kernel - 1, 0), (0, 0)])
    x, q, k, v, g = _scan_rows(cfg, lp, xfull, dt_raw)
    q, k = (jnp.where(live[..., None], z, 0.0) for z in (q, k))
    v = jnp.where(live[..., None, None], v, 0.0)
    g = jnp.where(live[..., None], g, 0.0)

    def one(q, k, v, g):
        rows = [jnp.pad(z, [(0, pad)] + [(0, 0)] * (z.ndim - 1))
                for z in (q, k, v, g)]
        s0 = jnp.zeros((k.shape[-1], v.shape[1] * v.shape[2]), jnp.float32)
        o, _, _ = la._ssd_chain(s0, la._ssd_blocks(*rows, block),
                                jnp.zeros((0,), jnp.int32))
        return o.reshape(t + pad, *v.shape[1:])[:t]

    return _skip(lp, jax.vmap(one)(q, k, v, g), x)


def hidden_states(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  seq_lens: jnp.ndarray | None = None, mesh=None) -> jnp.ndarray:
    """Final-norm hidden states [B, T, E], cache-free: each sequence from
    a zero state in the chunked form (jnp), plain causal attention."""
    b, t = tokens.shape
    x = _embed(cfg, params, tokens)
    if seq_lens is None:
        seq_lens = jnp.full((b,), t, jnp.int32)
    live = jnp.arange(t)[None] < seq_lens[:, None]

    def lin(lp, li, u, carry):
        z, pre, dt_raw = _project(cfg, lp, u)
        y = _free_scan(cfg, lp, pre, dt_raw, live)
        return _gated_out(cfg, lp, y, z, u.dtype), carry, None

    def att(lp, pi, u):
        q, k, v = _qkv(cfg, lp, u)
        o = attention_prefill(q, k, v, seq_lens, use_pallas=cfg.use_pallas,
                              mesh=mesh)
        return _attn_out(lp, o), None

    x, _, _, _ = _stack(params, cfg, x, None, lin, att)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            mesh=None) -> jnp.ndarray:
    """Cache-free full forward: tokens [B, T] -> logits [B, T, V] (fp32)."""
    return _unembed(cfg, params, hidden_states(params, cfg, tokens, mesh=mesh))


# ---------------------------------------------------------------------------
# through the cache: a chunk region, a step region
# ---------------------------------------------------------------------------


def _chunk_region(cfg: ModelConfig, lp: Params, li, rec: RecurrentState,
                  rows, slot, start, length, save_pos, block: int):
    """One state-space layer on ONE slot's chunk rows (`rows` = the
    convolution's input and dt's pre-activation for them, [c, ...]) at
    positions start + i, `length` of them live. Returns (y [c, H, dv],
    rec with the slot's state and convolution tail after the chunk and
    nothing pending, (states, tails) at `save_pos` [SAVES]): olmo_hybrid's
    `_chunk_region` for a scan without the delta."""
    pre, dt_raw = rows
    c = pre.shape[0]
    taps = cfg.linear_conv_kernel - 1
    fresh = start == 0
    tail0 = jnp.where(fresh, 0, rec.conv[li, slot].reshape(taps, -1))
    xfull = jnp.concatenate([tail0, pre])                    # [taps + c, C]
    x, q, k, v, g = _scan_rows(cfg, lp, xfull, dt_raw)
    # rows that hold no token are ZEROS to the scan, whatever an earlier
    # layer's kernel left in them (0 x NaN: PERF.md, PR 42)
    live = (jnp.arange(c) < length)[:, None]
    q, k, g = (jnp.where(live, z, 0.0) for z in (q, k, g))
    v = jnp.where(live[..., None], v, 0.0)
    s0 = jnp.where(fresh, 0.0, rec.state[li, slot])
    passed = (save_pos > start) & (save_pos <= start + length)
    rel = jnp.where(passed, save_pos - start, 0)
    keep = jnp.where(passed, rel // block - 1, -1)
    o, s1, kept = la.ssd_chunk(s0, q, k, v, g, keep, block,
                               use_pallas=cfg.use_pallas)

    def tail_at(r):           # the rows before position start + r
        return jax.lax.dynamic_slice_in_dim(xfull, r, taps)

    rec = dataclasses.replace(
        rec, state=_at(rec.state, li, s1, slot),
        conv=_at(rec.conv, li, tail_at(length).reshape(-1), slot))
    return _skip(lp, o, x), rec, (
        kept, jax.vmap(tail_at)(rel).reshape(rel.shape[0], -1))


def _step_region(cfg: ModelConfig, lp: Params, li, rec: RecurrentState,
                 rows, active):
    """One state-space layer on t rows of EVERY slot (`rows` [S, t, ...]):
    commits each live slot's pending rows (state and convolution tail),
    runs the new rows on top, leaves them pending. Returns (y [S, t, H,
    dv], rec)."""
    # a slot that is not live runs rows of zeros
    pre, dt_raw = (jnp.where(active[:, None, None], z, 0) for z in rows)
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
    x, q, k, v, g = _scan_rows(cfg, lp, xfull, dt_raw)
    pend = (rec.pend_k[li][:, :, 0],
            rec.pend_v[li].reshape(s, -1, *v.shape[2:]), rec.pend_g[li])
    state, o = la.ssd_step(rec.state, li, pend, n, q, k, v, g, active,
                           use_pallas=cfg.use_pallas)
    rows_ = slice(0, t)
    rec = dataclasses.replace(
        rec, state=state,
        conv=_at(rec.conv, li, jnp.where(
            active[:, None], tail.reshape(s, -1), rec.conv[li])),
        pend_x=_at(rec.pend_x, li, pre.reshape(s, -1), slice(None),
                   slice(0, t * ch)),
        pend_k=_at(rec.pend_k, li, k[:, :, None], slice(None), rows_),
        pend_v=_at(rec.pend_v, li, v.reshape(s, -1), slice(None),
                   slice(0, t * v.shape[2] * v.shape[3])),
        pend_g=_at(rec.pend_g, li, g, slice(None), rows_))
    return _skip(lp, o, x), rec


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
    block = ssd_block(cache.page_size)
    assert c % block == 0, f"a chunk of {c} rows is not whole blocks of {block}"
    none = jnp.full((SAVES,), -1, jnp.int32)
    save_pos, save_idx = state_io if state_io is not None else (none, none)
    dt = params["embed"].dtype
    xc = _embed(cfg, params, chunk_tokens) if embeds is None else embeds
    x = jnp.concatenate([xc.astype(dt), _embed(cfg, params, tokens)])[None]
    total = chunk_start + chunk_len
    positions = cache.lengths

    def lin(lp, li, u, rec):
        z, pre, dtr = _project(cfg, lp, u[0])
        y, rec, saved = _chunk_region(
            cfg, lp, li, rec, (pre[:c], dtr[:c]), slot, chunk_start,
            chunk_len, save_pos, block)
        yg, rec = _step_region(
            cfg, lp, li, rec, (pre[c:, None], dtr[c:, None]), active)
        y = jnp.concatenate([y, yg[:, 0]])
        return _gated_out(cfg, lp, y, z, u.dtype)[None], rec, saved

    def att(lp, pi, u):
        q, k, v = _qkv(cfg, lp, u[0])
        oc, og = ragged_paged_attention(
            cache.k, cache.v, cache.page_size, layer=pi,
            use_pallas=cfg.use_pallas,
            q_chunk=q[None, :c], chunk_row=table_row, chunk_start=chunk_start,
            chunk_total=total, k_chunk=k[:c], v_chunk=v[:c],
            q_group=q[c:, None], page_table=cache.page_table,
            group_lengths=positions, k_group=k[c:, None], v_group=v[c:, None])
        return _attn_out(lp, jnp.concatenate([oc[0], og[:, 0]]))[None], (k, v)

    x, rec, saved, (k_new, v_new) = _stack(params, cfg, x, cache.rec, lin, att)
    rec = _save_snapshots(rec, saved, save_idx)
    x = rms_norm(x[0], params["final_norm"], cfg.rms_eps)
    chunk_logits = _unembed(cfg, params, x[jnp.maximum(chunk_len - 1, 0)])
    dec_logits = _unembed(cfg, params, x[c:])
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
    lengths + i. Returns (final-norm x [S, t, E], the attention layers' K
    and V [periods, S, t, KVH, D], rec with the rows pending)."""
    x = _embed(cfg, params, tokens)
    base = cache.lengths

    def lin(lp, li, u, rec):
        z, pre, dtr = _project(cfg, lp, u)
        y, rec = _step_region(cfg, lp, li, rec, (pre, dtr), active)
        return _gated_out(cfg, lp, y, z, u.dtype), rec, None

    def att(lp, pi, u):
        q, k, v = _qkv(cfg, lp, u)
        _, o = ragged_paged_attention(
            cache.k, cache.v, cache.page_size, layer=pi,
            use_pallas=cfg.use_pallas, q_group=q,
            page_table=cache.page_table, group_lengths=base, k_group=k,
            v_group=v)
        return _attn_out(lp, o), (k, v)

    x, rec, _, (k_new, v_new) = _stack(params, cfg, x, cache.rec, lin, att)
    return rms_norm(x, params["final_norm"], cfg.rms_eps), k_new, v_new, rec


def decode_step(params: Params, cfg: ModelConfig, tokens, cache, active,
                mesh=None):
    """One decode step for ALL slots (llama.decode_step's contract)."""
    del mesh
    positions = cache.lengths
    x, k_new, v_new, rec = _step_launch(params, cfg, tokens[:, None], cache,
                                        active)
    logits = _unembed(cfg, params, x[:, 0])
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
    t = tokens.shape[1]
    x, k_new, v_new, rec = _step_launch(params, cfg, tokens, cache, active)
    logits = _unembed(cfg, params, x)
    positions = cache.lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    k_pool, v_pool = write_multi_all(
        cache.k, cache.v, k_new, v_new, cache.page_table, positions, active,
        cache.page_size, use_pallas=cfg.use_pallas)
    rec = dataclasses.replace(rec, pend_n=jnp.where(active, t, 0))
    return logits, dataclasses.replace(cache, k=k_pool, v=v_pool, rec=rec)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


@jax.jit
def _ssm_leaves(key, proto):
    """A_log and dt_bias as Mamba-2's published initialisation draws them:
    A uniform in (1, 16), the step dt log-uniform in (0.001, 0.1) through
    softplus's inverse. One jit, like every random leaf."""
    ka, kd = jax.random.split(key)
    a = jax.random.uniform(ka, proto.shape, jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(
        kd, proto.shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
    return (jnp.log(a).astype(proto.dtype),
            (dt + jnp.log(-jnp.expm1(-dt))).astype(proto.dtype))


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params (tests, the benchmark's seeded weights): normal
    at fan-in^-0.5 (the convolution's taps K^-0.5 and its bias 0.1), A_log
    and dt_bias as `_ssm_leaves` draws them, D = 1. The embedding at 0.02 /
    embedding_multiplier, so that h0 has the scale other families' 0.02
    gives theirs: at 0.02 the tied head reads the last token's own
    embedding back at ten times the largest other logit (12 |E|^2 against
    |E|), and every seeded stream repeats one token, which speculation
    then accepts whole (read on the chip, PR 61). W_q and W_k times
    (attention_multiplier x sqrt(head))^-0.5 each, so that the scores have
    the deviation head^-0.5 gives other families' (about 1): at fan-in
    scale and 1/64 they are 0.125 apart, the softmax is flat, the layer is
    a mean over the context and neither a rotary embedding nor the scale
    itself moves a logit (the controls that read like a sound run, PR 61)."""
    e, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, dv = cfg.linear_num_heads, cfg.linear_value_head_dim
    di, ch, taps = h * dv, cfg.conv_channels, cfg.linear_conv_kernel
    hd, kvd = (cfg.num_heads * cfg.head_dim_, cfg.num_kv_heads * cfg.head_dim_)
    period, _ = _period(cfg)
    n = cfg.num_layers // period
    ks = iter(jax.random.split(key, 16 * period + 8))
    sharp = (cfg.attention_multiplier * cfg.head_dim_ ** 0.5) ** -0.5

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

    def mamba():
        a_log, dt_bias = _ssm_leaves(next(ks), jnp.zeros((n, h), dtype))
        return {
            **mlp(),
            "w_in": w(n, e, di + ch + h), "wo": w(n, di, e),
            "conv_w": w(n, taps, ch, scale=taps ** -0.5),
            "conv_b": w(n, ch, scale=0.1),
            "A_log": a_log, "dt_bias": dt_bias,
            "D": jnp.ones((n, h), dtype),
            "o_norm": jnp.ones((n, di), dtype),
        }

    params: Params = {
        "embed": w(v, e, scale=0.02 / cfg.embedding_multiplier),
        "mamba": tuple(mamba() for _ in range(period - 1)),
        "attn": {
            **mlp(),
            "wq": w(n, e, hd, scale=sharp * e ** -0.5),
            "wk": w(n, e, kvd, scale=sharp * e ** -0.5), "wv": w(n, e, kvd),
            "wo": w(n, hd, e),
        },
        "final_norm": jnp.ones((e,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = w(e, v, scale=0.02)
    return params
