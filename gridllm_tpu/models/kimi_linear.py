"""Kimi-Linear decoder (kimi-linear:48b, PR 51): Kimi Delta Attention 3:1
with latent attention without positions, routed experts of which this
chip may hold a share.

Nothing here is new mathematics: the module composes what three families
already run, and owns only what is Kimi's: the block (pre-norm residual),
the KDA mixer's projections (low-rank decay and output gate) and which
layer is which.

- a KDA layer (`cfg.layer_types[l] == "linear_attention"`) is the delta
  rule of ops/linear_attn.py with a log decay a head a KEY CHANNEL
  (`cfg.linear_channel_decay`): its state and pending rows live in
  `PagedKVCache.rec` and move through `olmo_hybrid._chunk_region` /
  `_step_region` (`kda_chunk` / `kda_step` by the decay's shape);
- an MLA layer ("full_attention") is DeepSeek-V2's latent attention with
  the rotation off (`deepseek._project(inv_freq=None)`): the cache row is
  `[c, k_r]`, read absorbed from the latent pool whose leading axis is the
  MLA layers only;
- the feed-forward is `llama._mlp` in the first `cfg.first_k_dense` layers
  and `mixtral._moe_mlp` after them: sigmoid scores, a selection bias
  (`cfg.router_bias`), the chosen weights normalised and scaled, one shared
  expert, and of the routed experts those `cfg.held_experts` says.

Layers are unrolled, one tree a layer (`params["layers"]`, a tuple): the
published pattern is six periods of four and a last one of three behind a
dense first layer, which no single scan body holds, and a chip holds eight
of them. One layer body (`_stack`'s loop) serves every phase; a phase is a
pair of closures (`lin`, `att`). benchmark/reference/kimi_linear_f32.py
states the equations.

The entry points are the ones an engine launches, and `validate_mesh`
refuses every mesh: `hidden_states` (/api/embed), `decode_step`,
`verify_step`, `mixed_step` (every prompt, chunk by chunk) and
`commit_verify`. There is no `prefill` / `prefill_chunk`.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from gridllm_tpu.models import deepseek, llama, mixtral
from gridllm_tpu.models import olmo_hybrid as oh
from gridllm_tpu.models.configs import ModelConfig
from gridllm_tpu.models.olmo_hybrid import (  # noqa: F401  (engine hooks)
    SAVES,
    commit_verify,
    new_state,
)
from gridllm_tpu.ops.kvcache import (
    write_decode_all,
    write_multi_all,
    write_prefill_all,
)
from gridllm_tpu.ops.layers import rms_norm

Params = dict[str, Any]

# the engine asks decode_step / verify_step for the routed statistics
STEP_STATS = True


def validate_mesh(cfg: ModelConfig, mesh) -> None:
    """No mesh: the latent row has one head, the state's packed heads have
    no sharding written, and the experts' exchange over `ep` is not built
    (a share is held by `cfg.experts_held`, on one chip)."""
    if mesh is not None:
        raise ValueError(
            f"{cfg.name}: kimi_linear is served on one device only (no "
            "sharding of the latent pool, the recurrent state or the "
            "experts' exchange has been written)")


def _is_linear(cfg: ModelConfig, i: int) -> bool:
    return cfg.layer_types[i] == "linear_attention"


# ---------------------------------------------------------------------------
# the KDA mixer's row-wise parts
# ---------------------------------------------------------------------------


def _project(lp: Params, x: jnp.ndarray):
    """x [..., E] (normed) -> (the rows the convolution reads [..., C] (q,
    k, v side by side), the output gate's pre-activation [..., H*dv], the
    decay's [..., H*dk] (both through a low-rank pair), beta's [..., H])."""
    p = llama._precision(x)

    def dot(a, w):
        return jnp.dot(a, lp[w], precision=p)

    pre = jnp.concatenate([dot(x, w) for w in ("wq", "wk", "wv")], axis=-1)
    return (pre, dot(dot(x, "wg_down"), "wg_up"),
            dot(dot(x, "wf_down"), "wf_up"), dot(x, "wb"))


def _kda_out(cfg: ModelConfig, lp: Params, o, gate, dtype):
    """W_o [RMSNorm_dv(o) * sigmoid(gate)]."""
    return oh._gated_out(cfg, lp, o, gate, dtype, act=jax.nn.sigmoid)


# ---------------------------------------------------------------------------
# one layer body, one stack runner
# ---------------------------------------------------------------------------


def _stack(params: Params, cfg: ModelConfig, x, rec, lin, att, live):
    """Every layer on x [B, T, E]. `lin(lp, li, h, rec) -> (mixed, rec,
    ys)` with li the KDA layer's index in the state, `att(lp, pi, h) ->
    (mixed, ys)` with pi the latent pool's layer; h the normed input.
    Returns (x, rec, the KDA layers' ys, the MLA layers' ys, the expert
    layers' statistics summed)."""
    li = pi = 0
    lys, fys, stats = [], [], 0
    for i, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        if _is_linear(cfg, i):
            mixed, rec, y = lin(lp, li, h, rec)
            lys.append(y)
            li += 1
        else:
            mixed, y = att(lp, pi, h)
            fys.append(y)
            pi += 1
        x = x + mixed
        m = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        if i < cfg.first_k_dense:
            x = x + llama._mlp(lp, m)
        else:
            y, st = mixtral._moe_mlp(cfg, None, live, lp, m)
            x, stats = x + y, stats + st
    return x, rec, lys, fys, stats


def _wo(lp: Params, a: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(a, lp["wo"], precision=llama._precision(a))


def hidden_states(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  seq_lens: jnp.ndarray | None = None, mesh=None) -> jnp.ndarray:
    """Final-norm hidden states [B, T, E], cache-free: each sequence from
    a zero state in the chunked form (jnp), the expanded latent form."""
    del mesh
    b, t = tokens.shape
    x = params["embed"][tokens]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    if seq_lens is None:
        seq_lens = jnp.full((b,), t, jnp.int32)
    live = pos < seq_lens[:, None]

    def lin(lp, li, h, rec):
        pre, gate, a, bb = _project(lp, h)
        o = oh._free_delta(cfg, lp, pre, a, bb, live)
        return _kda_out(cfg, lp, o, gate, h.dtype), rec, None

    def att(lp, pi, h):
        q_nope, q_pe, row = deepseek._project(cfg, lp, h, pos, None)
        return _wo(lp, deepseek._expanded(
            cfg, lp, q_nope, q_pe, pos, row, pos, live)), None

    x, *_ = _stack(params, cfg, x, None, lin, att, live)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            mesh=None) -> jnp.ndarray:
    """Cache-free full forward: tokens [B, T] -> logits [B, T, V] (fp32)."""
    return llama._unembed(cfg, params, hidden_states(params, cfg, tokens))


def _mla(cfg: ModelConfig, lp: Params, pi, h, pos, attend):
    """An MLA layer around one of deepseek's `attend` closures over the
    latent pool: (the mixer's output, the layer's cache rows)."""
    q_nope, q_pe, row = deepseek._project(cfg, lp, h, pos, None)
    return _wo(lp, attend(lp, pi, q_nope, q_pe, row)), row


def mixed_step(params: Params, cfg: ModelConfig, chunk_tokens, chunk_start,
               chunk_len, slot, table_row, tokens, cache, active, mesh=None,
               embeds=None, state_io=None):
    """One fused chunked-prefill + decode step (llama.mixed_step's
    contract): rows [0, C) the admitting slot's chunk against its latent
    prefix and its carried state, rows [C, C + S) one decode token a slot.
    `state_io` = (positions [SAVES], snapshot entries [SAVES]): page
    boundaries this chunk passes at which the state is saved."""
    del mesh
    c = chunk_tokens.shape[0]
    block = oh.gdn_block(cache.page_size)
    assert c % block == 0, f"a chunk of {c} rows is not whole blocks of {block}"
    none = jnp.full((SAVES,), -1, jnp.int32)
    save_pos, save_idx = state_io if state_io is not None else (none, none)
    dt = params["embed"].dtype
    xc = params["embed"][chunk_tokens] if embeds is None else embeds
    x = jnp.concatenate([xc.astype(dt), params["embed"][tokens]])[None]
    total = chunk_start + chunk_len
    positions = cache.lengths
    pos = jnp.concatenate(
        [chunk_start + jnp.arange(c, dtype=jnp.int32), positions])[None]
    live = jnp.concatenate([jnp.arange(c) < chunk_len, active])[None]

    def lin(lp, li, h, rec):
        pre, gate, a, bb = _project(lp, h[0])
        o, rec, saved = oh._chunk_region(
            cfg, lp, li, rec, (pre[:c], a[:c], bb[:c]), slot, chunk_start,
            chunk_len, save_pos, block)
        og, rec = oh._step_region(
            cfg, lp, li, rec, (pre[c:, None], a[c:, None], bb[c:, None]),
            active)
        o = jnp.concatenate([o, og[:, 0]])
        return _kda_out(cfg, lp, o, gate, h.dtype)[None], rec, saved

    attend = deepseek._chunk_attend(
        cfg, cache, table_row, chunk_start, total, c,
        group=(cache.page_table, positions))

    def att(lp, pi, h):
        return _mla(cfg, lp, pi, h, pos, attend)

    x, rec, saved, rows, _ = _stack(params, cfg, x, cache.rec, lin, att, live)
    # per KDA layer (states [SAVES, ..], tails [SAVES, ..]) -> [Ll, SAVES, ..]
    states, tails = (jnp.stack(z) for z in zip(*saved))
    rec = oh._save_snapshots(rec, (states[None], tails[None]), save_idx)
    x = rms_norm(x[0], params["final_norm"], cfg.rms_eps)
    chunk_logits = llama._unembed(cfg, params, x[jnp.maximum(chunk_len - 1, 0)])
    dec_logits = llama._unembed(cfg, params, x[c:])
    rows = jnp.stack(rows)[:, 0, :, None]             # [Lc, C + S, 1, R + dr]
    k_pool, _ = write_prefill_all(
        cache.k, None, rows[:, :c], None, table_row, chunk_start, chunk_len,
        cache.page_size, use_pallas=cfg.use_pallas)
    k_pool, _ = write_decode_all(
        k_pool, None, rows[:, c:], None, cache.page_table, positions, active,
        cache.page_size, use_pallas=cfg.use_pallas)
    rec = dataclasses.replace(
        rec, pend_n=active.astype(jnp.int32).at[slot].set(0))
    new_lengths = jnp.minimum(
        cache.lengths + active.astype(jnp.int32), cache.max_context
    ).at[slot].set(total)
    return chunk_logits, dec_logits, dataclasses.replace(
        cache, k=k_pool, rec=rec,
        page_table=cache.page_table.at[slot].set(table_row),
        lengths=new_lengths)


def _step_launch(params: Params, cfg: ModelConfig, tokens, cache, active):
    """t rows of every slot (decode: 1, verify: K + 1) at positions
    lengths + i. Returns (final-norm x [S, t, E], the MLA layers' cache
    rows [Lc, S, t, R + dr], rec with the rows pending, the statistics)."""
    s, t = tokens.shape
    x = params["embed"][tokens]
    base = cache.lengths
    pos = base[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    live = jnp.broadcast_to(active[:, None], tokens.shape)

    def lin(lp, li, h, rec):
        pre, gate, a, bb = _project(lp, h)
        o, rec = oh._step_region(cfg, lp, li, rec, (pre, a, bb), active)
        return _kda_out(cfg, lp, o, gate, h.dtype), rec, None

    attend = deepseek._group_attend(cfg, cache, base)

    def att(lp, pi, h):
        return _mla(cfg, lp, pi, h, pos, attend)

    x, rec, _, rows, stats = _stack(params, cfg, x, cache.rec, lin, att, live)
    return (rms_norm(x, params["final_norm"], cfg.rms_eps), jnp.stack(rows),
            rec, stats)


def decode_step(params: Params, cfg: ModelConfig, tokens, cache, active,
                mesh=None, with_stats: bool = False):
    """One decode step for ALL slots (llama.decode_step's contract)."""
    del mesh
    positions = cache.lengths
    x, rows, rec, stats = _step_launch(params, cfg, tokens[:, None], cache,
                                       active)
    logits = llama._unembed(cfg, params, x[:, 0])
    k_pool, _ = write_decode_all(
        cache.k, None, rows[:, :, 0, None], None, cache.page_table,
        positions, active, cache.page_size, use_pallas=cfg.use_pallas)
    cache = dataclasses.replace(
        cache, k=k_pool,
        rec=dataclasses.replace(rec, pend_n=active.astype(jnp.int32)),
        lengths=jnp.minimum(cache.lengths + active.astype(jnp.int32),
                            cache.max_context))
    return (logits, cache, stats) if with_stats else (logits, cache)


def verify_step(params: Params, cfg: ModelConfig, tokens, cache, active,
                mesh=None, tree_pos=None, tree_mask=None,
                with_stats: bool = False):
    """One speculative-verify forward for ALL slots (llama.verify_step's
    contract: candidate rows written optimistically, lengths unchanged).
    The state is left with all K + 1 rows pending: `commit_verify` sets
    how many count, as `rollback_to_length` does for the pages."""
    del mesh
    if tree_pos is not None or tree_mask is not None:
        raise NotImplementedError(
            f"{cfg.name}: tree verification is not served for a recurrent "
            "state (a state has one past, not a tree of them)")
    s, t = tokens.shape
    x, rows, rec, stats = _step_launch(params, cfg, tokens, cache, active)
    logits = llama._unembed(cfg, params, x)
    positions = cache.lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    k_pool, _ = write_multi_all(
        cache.k, None, rows[..., None, :], None, cache.page_table, positions,
        active, cache.page_size, use_pallas=cfg.use_pallas)
    cache = dataclasses.replace(
        cache, k=k_pool,
        rec=dataclasses.replace(rec, pend_n=jnp.where(active, t, 0)))
    return (logits, cache, stats) if with_stats else (logits, cache)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params (tests, the benchmark's seeded weights): normal
    at fan-in^-0.5 (router, embedding and head 0.02), the decay's leaves
    as the delta rule's published initialisation draws them
    (`olmo_hybrid._decay_leaves`), the selection bias normal at 0.05 so
    that it changes some choices. Expert leaves hold the HELD experts."""
    e, v = cfg.hidden_size, cfg.vocab_size
    h, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    mh, r, dr = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, mv = cfg.qk_nope_head_dim, cfg.v_head_dim
    X, f = cfg.num_experts, cfg.expert_width
    held = cfg.held_experts[1]
    fs = cfg.num_shared_experts * f
    low = dk                 # the low-rank width of the decay's and gate's pair
    keys = iter(jax.random.split(key, 32 * cfg.num_layers + 8))

    def w(*shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5)
        return mixtral._normal_leaf(
            next(keys), shape=shape, scale=scale, dtype=dtype)

    def layer(i: int) -> Params:
        lp: Params = {"attn_norm": jnp.ones((e,), dtype),
                      "mlp_norm": jnp.ones((e,), dtype)}
        if _is_linear(cfg, i):
            a_log, _ = oh._decay_leaves(next(keys), jnp.zeros((h,), dtype))
            _, dt_bias = oh._decay_leaves(
                next(keys), jnp.zeros((h * dk,), dtype))
            lp.update(
                wq=w(e, h * dk), wk=w(e, h * dk), wv=w(e, h * dv),
                wf_down=w(e, low), wf_up=w(low, h * dk),
                wg_down=w(e, low), wg_up=w(low, h * dv),
                wb=w(e, h), wo=w(h * dv, e),
                conv_w=w(cfg.linear_conv_kernel, cfg.conv_channels,
                         scale=cfg.linear_conv_kernel ** -0.5),
                A_log=a_log, dt_bias=dt_bias, o_norm=jnp.ones((dv,), dtype))
        else:
            lp.update(
                wq=w(e, mh * (dn + dr)), w_kva=w(e, r + dr),
                kv_norm=jnp.ones((r,), dtype), w_kvb=w(r, mh * (dn + mv)),
                wo=w(mh * mv, e))
        if i < cfg.first_k_dense:
            fi = cfg.intermediate_size
            lp.update(w_gate=w(e, fi), w_up=w(e, fi), w_down=w(fi, e))
        else:
            lp.update(
                router=w(e, X, scale=0.02), router_bias=w(X, scale=0.05),
                we_gate=w(held, e, f), we_up=w(held, e, f),
                we_down=w(held, f, e))
            if fs:
                lp.update(ws_gate=w(e, fs), ws_up=w(e, fs), ws_down=w(fs, e))
        return lp

    params: Params = {
        "embed": w(v, e, scale=0.02),
        "layers": tuple(layer(i) for i in range(cfg.num_layers)),
        "final_norm": jnp.ones((e,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = w(e, v, scale=0.02)
    return params
