"""DeepSeek-V2 decoder (deepseek-v2-lite:16b, PR 36): latent attention
(MLA) over a latent page pool, leading dense layers ahead of routed ones
with shared experts.

A module of its own, not the llama skeleton widened: nothing of
`llama._qkv` survives (queries of nope + rope parts, ONE latent row and
one RoPE key a token shared by every head, a value head narrower than a
key head), the cache row is not K and V per head, and the first
`cfg.first_k_dense` layers have no router, so the one stacked tree under
llama's scan does not hold the model. What is shared is called, not
copied: the routed feed-forward is `mixtral._moe_mlp` (the shared experts
and the un-renormalised top-k are data of the config there), the dense one
`llama._mlp`, the head `llama._unembed`, and every read of the pool is
`ops.attention.ragged_paged_attention`.

One layer body (`_layer`) and one stack runner (`_stack`: the dense
layers unrolled ahead of ONE scan over the routed layers) serve every
phase; a phase is an `attend` closure. The entry points are the ones an
engine launches, and `validate_mesh` refuses every mesh: `hidden_states`
(/api/embed), `decode_step`, `verify_step` and `mixed_step`, which admits
every prompt chunk by chunk. There is no `prefill` / `prefill_chunk`: only
an `sp` or `pp` engine calls those.

Params: `dense` and `layers` are two stacked trees ([first_k_dense, ...]
and [num_layers - first_k_dense, ...]); pool layer l is dense layer l or
routed layer l - first_k_dense.

The cache row of a token in a layer is `[c (kv_lora_rank), k_pe
(qk_rope_head_dim)]`: 576 values at DeepSeek-V2-Lite, against 16 x (192 +
128) per head. Two forms of the same attention read it:

- ABSORBED (decode, verify, and the chunk region): the key
  half of `w_kvb` is folded into the query (`q' = q_nope Wk^T`, 512 wide)
  and the value half applied to the output (`a = o Wv`), so attention runs
  straight on rows: scores over all 576 values of a row, values its first
  512, all heads on the one cache head, each page read once
  (`ragged_paged_attention(latent_dv=...)`);
- EXPANDED (`forward`, `hidden_states`): K and V rebuilt per head
  from the latents, plain attention at 192 / 128 a head.

The chunk region is absorbed because one v5e chip read it 2-4 times
faster than the expanded form for a 512-row chunk behind prefixes of 0 to
4 k (PERF.md, PR 36; `deploy/tpu_mla_forms.py` times both).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from gridllm_tpu.models import llama, mixtral
from gridllm_tpu.models.configs import ModelConfig
from gridllm_tpu.ops.attention import ragged_paged_attention
from gridllm_tpu.ops.kvcache import (
    PagedKVCache,
    write_decode_all,
    write_multi_all,
    write_prefill_all,
)
from gridllm_tpu.ops.layers import (
    apply_rope,
    precompute_rope,
    rms_norm,
    yarn_factors,
)

Params = dict[str, Any]

# the engine asks decode_step / verify_step for the routed statistics
STEP_STATS = True

# attend(lp, li, q_nope [B,T,H,dn], q_pe [B,T,H,dr], row [B,T,R]) -> [B,T,H*dv]
Attend = Callable[..., jnp.ndarray]


def validate_mesh(cfg: ModelConfig, mesh) -> None:
    """No mesh: the latent row is shared by every head, so tp would
    replicate the pool and split nothing of it, and no sharding of this
    family's trees has been proved. Refused rather than run unproved."""
    if mesh is not None:
        raise ValueError(
            f"{cfg.name}: deepseek_v2 is served on one device only (a "
            "latent cache has one head: no mesh axis splits it)")


def softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-0.5 times YaRN's m^2."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * (
        yarn_factors(cfg.rope_scaling)[0])


def _split_kvb(cfg: ModelConfig, lp: Params):
    """w_kvb [R, H * (dn + dv)] → Wk [R, H, dn], Wv [R, H, dv]."""
    r, h = cfg.kv_lora_rank, cfg.num_heads
    w = lp["w_kvb"].reshape(r, h, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _project(cfg: ModelConfig, lp: Params, h: jnp.ndarray, pos: jnp.ndarray,
             inv_freq: jnp.ndarray):
    """Normed state h [B, T, E] at positions pos [B, T] → q_nope
    [B, T, H, dn], q_pe [B, T, H, dr] (rotated), row [B, T, R + dr]: the
    cache row, the normed latent and its rotated key. `inv_freq` None: no
    positional encoding (kimi_linear's latent layers), nothing rotates.
    `cfg.q_lora_rank`: the query through a low-rank pair and its norm;
    `cfg.mla_scales`: the query and the normed latent each times a
    constant (longcat_flash; 1 and 1 elsewhere)."""
    p = llama._precision(h)
    b, t, _ = h.shape
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    s_q, s_kv = cfg.mla_scales
    if cfg.q_lora_rank:
        # longcat_flash: q = W_qb RMSNorm(W_qa h), times sqrt(E / rank)
        with jax.named_scope("mla_qlora"):
            cq = rms_norm(jnp.dot(h, lp["w_qa"], precision=p), lp["q_norm"],
                          cfg.rms_eps)
            q = jnp.dot(cq, lp["w_qb"], precision=p,
                        preferred_element_type=jnp.float32)
            q = (q * s_q).astype(h.dtype)
    else:
        q = jnp.dot(h, lp["wq"], precision=p)
    q = q.reshape(b, t, cfg.num_heads, dn + dr)
    kva = jnp.dot(h, lp["w_kva"], precision=p)
    kv_norm = lp["kv_norm"]
    if s_kv != 1.0:     # the scale inside the norm's float32 product
        kv_norm = kv_norm.astype(jnp.float32) * s_kv
    c = rms_norm(kva[..., :cfg.kv_lora_rank], kv_norm, cfg.rms_eps)
    if inv_freq is None:
        return q[..., :dn], q[..., dn:], jnp.concatenate(
            [c, kva[..., cfg.kv_lora_rank:]], axis=-1)
    q_pe = apply_rope(q[..., dn:], pos, inv_freq)
    k_pe = apply_rope(kva[..., None, cfg.kv_lora_rank:], pos, inv_freq)[..., 0, :]
    mult = yarn_factors(cfg.rope_scaling)[1]
    if mult != 1.0:
        q_pe, k_pe = q_pe * mult, k_pe * mult
    return q[..., :dn], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def _absorbed(cfg: ModelConfig, lp: Params, q_nope, q_pe, attend_rows):
    """The absorbed form around a read of latent rows. `attend_rows(q
    [B, T, H, R + dr]) -> o [B, T, H, R]` is the paged attention; its
    queries come scaled so that its own rsqrt(row width) nets
    `softmax_scale`."""
    wk, wv = _split_kvb(cfg, lp)
    p = llama._precision(q_nope)
    width = cfg.cache_dim
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, wk, precision=p,
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat, q_pe.astype(jnp.float32)], axis=-1)
        q = (q * (softmax_scale(cfg) * math.sqrt(width))).astype(q_nope.dtype)
    o = attend_rows(q)
    with jax.named_scope("mla_absorb"):
        a = jnp.einsum("bthr,rhd->bthd", o, wv, precision=p)
    return a.reshape(*a.shape[:2], -1)


def _expanded(cfg: ModelConfig, lp: Params, q_nope, q_pe, q_pos, rows,
              k_pos, k_valid):
    """The expanded form: K and V rebuilt per head from latent `rows`
    [B, N, R + dr] (keys at positions k_pos [B, N], live where k_valid),
    plain causal attention for queries at q_pos [B, T]. Softmax in
    float32."""
    p = llama._precision(q_nope)
    r = cfg.kv_lora_rank
    wk, wv = _split_kvb(cfg, lp)
    with jax.named_scope("mla_expand"):
        k_nope = jnp.einsum("bnr,rhd->bnhd", rows[..., :r], wk, precision=p)
        v = jnp.einsum("bnr,rhd->bnhd", rows[..., :r], wv, precision=p)
    scores = (
        jnp.einsum("bthd,bnhd->bhtn", q_nope, k_nope, precision=p,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bthd,bnd->bhtn", q_pe, rows[..., r:], precision=p,
                     preferred_element_type=jnp.float32)
    ) * softmax_scale(cfg)
    ok = k_valid[:, None, None, :] & (
        k_pos[:, None, None, :] <= q_pos[:, None, :, None])
    probs = jax.nn.softmax(jnp.where(ok, scores, -1e30), axis=-1)
    a = jnp.einsum("bhtn,bnhd->bthd", probs.astype(v.dtype), v, precision=p,
                   preferred_element_type=jnp.float32).astype(q_nope.dtype)
    return a.reshape(*a.shape[:2], -1)


def _layer(cfg: ModelConfig, lp: Params, x, pos, inv_freq, attend: Attend,
           li, mlp):
    """One decoder layer on x [B, T, E] → (x, its cache rows [B, T, R+dr],
    the feed-forward's statistics)."""
    pre = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q_nope, q_pe, row = _project(cfg, lp, pre, pos, inv_freq)
    att = attend(lp, li, q_nope, q_pe, row)
    x = x + jnp.dot(att, lp["wo"], precision=llama._precision(x))
    y, stats = mlp(lp, rms_norm(x, lp["mlp_norm"], cfg.rms_eps))
    return x + y, row, stats


def _stack(params: Params, cfg: ModelConfig, x, pos, attend: Attend,
           mesh=None, live=None):
    """Every layer on x [B, T, E]: the leading dense layers one by one,
    then ONE scan over the routed layers (one compiled body for layers
    first_k_dense..). Returns (x, rows [L, B, T, R + dr], the routed
    layers' statistics [L - first_k_dense, 2])."""
    inv_freq = precompute_rope(
        cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling)
    kd = cfg.first_k_dense
    dense_rows = []
    for i in range(kd):
        lp = jax.tree.map(lambda a: a[i], params["dense"])
        x, row, _ = _layer(cfg, lp, x, pos, inv_freq, attend, jnp.int32(i),
                           lambda lp, h: (llama._mlp(lp, h), None))
        dense_rows.append(row)
    moe = partial(mixtral._moe_mlp, cfg, mesh, live)

    def body(x, xs):
        lp, li = xs
        # the whole stack and the index, for the grouped experts' kernel
        lp = {**lp, "layer_stack": (params["layers"], li - kd)}
        x, row, stats = _layer(cfg, lp, x, pos, inv_freq, attend, li, moe)
        return x, (row, stats)

    n = cfg.num_layers - kd
    x, (rows, stats) = jax.lax.scan(
        body, x, (params["layers"], kd + jnp.arange(n, dtype=jnp.int32)))
    if dense_rows:
        rows = jnp.concatenate([jnp.stack(dense_rows), rows])
    return x, rows, stats


# ---------------------------------------------------------------------------
# the phases: each an `attend` over `_stack`
# ---------------------------------------------------------------------------


def hidden_states(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  seq_lens: jnp.ndarray | None = None, mesh=None,
                  stack=None) -> jnp.ndarray:
    """Final-norm hidden states [B, T, E], cache-free: the expanded form.
    `stack` (here and in every phase below): another family's stack runner
    with `_stack`'s contract (models/longcat_flash.py), rows [pool layers,
    B, T, R + dr]."""
    b, t = tokens.shape
    x = params["embed"][tokens]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    if seq_lens is None:
        seq_lens = jnp.full((b,), t, jnp.int32)
    valid = pos < seq_lens[:, None]

    def attend(lp, li, q_nope, q_pe, row):
        return _expanded(cfg, lp, q_nope, q_pe, pos, row, pos, valid)

    x, _, _ = (stack or _stack)(params, cfg, x, pos, attend, mesh, valid)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            mesh=None, stack=None) -> jnp.ndarray:
    """Cache-free full forward: tokens [B, T] → logits [B, T, V] (fp32)."""
    return llama._unembed(cfg, params, hidden_states(
        params, cfg, tokens, mesh=mesh, stack=stack))


def _with_k(cache: PagedKVCache, k_pool, **kw) -> PagedKVCache:
    return PagedKVCache(
        k=k_pool, v=None, page_table=kw.get("page_table", cache.page_table),
        lengths=kw.get("lengths", cache.lengths), page_size=cache.page_size)


def _latent_read(cfg: ModelConfig, cache: PagedKVCache, li, **regions):
    """ragged_paged_attention on the latent pool."""
    return ragged_paged_attention(
        cache.k, None, cache.page_size, layer=li, use_pallas=cfg.use_pallas,
        latent_dv=cfg.kv_lora_rank, **regions)


def _chunk_attend(cfg: ModelConfig, cache: PagedKVCache, table_row, start,
                  total, c: int, group=None):
    """attend for rows [0, c) one slot's chunk at positions start + i
    against its paged prefix and, `group` = (page_table, lengths), rows
    [c, c + S) one decode token a slot (the mixed step): ONE absorbed
    ragged launch a layer."""
    def attend(lp, li, q_nope, q_pe, row):
        def read(q):
            regions = dict(
                q_chunk=q[:, :c], chunk_row=table_row, chunk_start=start,
                chunk_total=total, k_chunk=row[0, :c, None])
            if group is not None:
                # q [1, S, H, W], row [1, S, W]: one decode token a slot
                regions.update(
                    q_group=q[0, c:, None], page_table=group[0],
                    group_lengths=group[1], k_group=row[0, c:, None, None])
            oc, og = _latent_read(cfg, cache, li, **regions)
            return jnp.concatenate(
                [oc[0]] + ([] if og is None else [og[:, 0]]))[None]

        return _absorbed(cfg, lp, q_nope, q_pe, read)

    return attend


def _group_attend(cfg: ModelConfig, cache: PagedKVCache, base, tree_pos=None,
                  tree_mask=None):
    """attend for Td rows a slot (decode: 1, verify: K + 1) against each
    slot's paged prefix of `base` tokens: the absorbed form."""
    def attend(lp, li, q_nope, q_pe, row):
        return _absorbed(cfg, lp, q_nope, q_pe, lambda q: _latent_read(
            cfg, cache, li, q_group=q, page_table=cache.page_table,
            group_lengths=base, k_group=row[:, :, None],
            tree_pos=tree_pos, tree_mask=tree_mask)[1])

    return attend


def decode_step(params: Params, cfg: ModelConfig, tokens, cache, active,
                mesh=None, with_stats: bool = False, stack=None):
    """One decode step for ALL slots (llama.decode_step's contract)."""
    x = params["embed"][tokens][:, None]              # [S, 1, E]
    positions = cache.lengths
    x, rows, stats = (stack or _stack)(
        params, cfg, x, positions[:, None],
        _group_attend(cfg, cache, positions), mesh, active[:, None])
    x = rms_norm(x[:, 0], params["final_norm"], cfg.rms_eps)
    logits = llama._unembed(cfg, params, x)
    k_pool, _ = write_decode_all(
        cache.k, None, rows[:, :, 0, None], None, cache.page_table,
        positions, active, cache.page_size, use_pallas=cfg.use_pallas)
    cache = _with_k(cache, k_pool, lengths=jnp.minimum(
        cache.lengths + active.astype(jnp.int32), cache.max_context))
    if with_stats:
        return logits, cache, stats.sum(axis=0)
    return logits, cache


def verify_step(params: Params, cfg: ModelConfig, tokens, cache, active,
                mesh=None, tree_pos=None, tree_mask=None,
                with_stats: bool = False, stack=None):
    """One speculative-verify forward for ALL slots (llama.verify_step's
    contract: candidates written optimistically, lengths unchanged)."""
    s, t = tokens.shape
    x = params["embed"][tokens]                       # [S, T, E]
    base = cache.lengths
    rel = (jnp.asarray(tree_pos, jnp.int32) if tree_pos is not None
           else jnp.arange(t, dtype=jnp.int32))
    live = jnp.broadcast_to(active[:, None], tokens.shape)
    x, rows, stats = (stack or _stack)(
        params, cfg, x, base[:, None] + rel[None],
        _group_attend(cfg, cache, base, tree_pos, tree_mask), mesh, live)
    logits = llama._unembed(
        cfg, params, rms_norm(x, params["final_norm"], cfg.rms_eps))
    positions = base[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    k_pool, _ = write_multi_all(
        cache.k, None, rows[..., None, :], None, cache.page_table, positions,
        active, cache.page_size, use_pallas=cfg.use_pallas)
    cache = _with_k(cache, k_pool)
    if with_stats:
        return logits, cache, stats.sum(axis=0)
    return logits, cache


def mixed_step(params: Params, cfg: ModelConfig, chunk_tokens, chunk_start,
               chunk_len, slot, table_row, tokens, cache, active, mesh=None,
               embeds=None, stack=None):
    """One fused chunked-prefill + decode step (llama.mixed_step's
    contract): rows [0, C) the admitting slot's chunk, rows [C, C + S) one
    decode token a slot, one ragged launch a layer."""
    c = chunk_tokens.shape[0]
    xc = params["embed"][chunk_tokens] if embeds is None else embeds
    dt = params["embed"].dtype
    x = jnp.concatenate([xc.astype(dt), params["embed"][tokens]])[None]
    positions = cache.lengths
    total = chunk_start + chunk_len
    pos = jnp.concatenate(
        [chunk_start + jnp.arange(c, dtype=jnp.int32), positions])[None]
    live = jnp.concatenate([jnp.arange(c) < chunk_len, active])[None]
    x, rows, _ = (stack or _stack)(
        params, cfg, x, pos,
        _chunk_attend(cfg, cache, table_row, chunk_start, total, c,
                      group=(cache.page_table, positions)), mesh, live)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    chunk_logits = llama._unembed(
        cfg, params, x[0, jnp.maximum(chunk_len - 1, 0)])
    dec_logits = llama._unembed(cfg, params, x[0, c:])
    rows = rows[:, 0, :, None]                        # [L, C + S, 1, R + dr]
    # region writes target disjoint pages (the admitting slot is not yet
    # active), so the order is immaterial
    k_pool, _ = write_prefill_all(
        cache.k, None, rows[:, :c], None, table_row, chunk_start, chunk_len,
        cache.page_size, use_pallas=cfg.use_pallas)
    k_pool, _ = write_decode_all(
        k_pool, None, rows[:, c:], None, cache.page_table, positions, active,
        cache.page_size, use_pallas=cfg.use_pallas)
    new_lengths = jnp.minimum(
        cache.lengths + active.astype(jnp.int32), cache.max_context
    ).at[slot].set(total)
    return chunk_logits, dec_logits, _with_k(
        cache, k_pool, page_table=cache.page_table.at[slot].set(table_row),
        lengths=new_lengths)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params (tests, the benchmark's seeded weights)."""
    e, v, h = cfg.hidden_size, cfg.vocab_size, cfg.num_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kd, X, f = cfg.first_k_dense, cfg.num_experts, cfg.expert_width
    fs = cfg.num_shared_experts * f
    ks = iter(jax.random.split(key, 32))

    def w(*shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5)
        return mixtral._normal_leaf(
            next(ks), shape=shape, scale=scale, dtype=dtype)

    def attention(n: int) -> Params:
        return {
            "attn_norm": jnp.ones((n, e), dtype),
            "wq": w(n, e, h * (dn + dr)),
            "w_kva": w(n, e, r + dr),
            "kv_norm": jnp.ones((n, r), dtype),
            "w_kvb": w(n, r, h * (dn + dv)),
            "wo": w(n, h * dv, e),
            "mlp_norm": jnp.ones((n, e), dtype),
        }

    n = cfg.num_layers - kd
    params: Params = {
        "embed": w(v, e, scale=0.02),
        "dense": {
            **attention(kd),
            "w_gate": w(kd, e, cfg.intermediate_size),
            "w_up": w(kd, e, cfg.intermediate_size),
            "w_down": w(kd, cfg.intermediate_size, e),
        },
        "layers": {
            **attention(n),
            "router": w(n, e, X, scale=0.02),
            "we_gate": w(n, X, e, f),
            "we_up": w(n, X, e, f),
            "we_down": w(n, X, f, e),
        },
        "final_norm": jnp.ones((e,), dtype),
    }
    if fs:
        params["layers"].update(
            ws_gate=w(n, e, fs), ws_up=w(n, e, fs), ws_down=w(n, fs, e))
    if not cfg.tie_embeddings:
        params["lm_head"] = w(e, v, scale=0.02)
    return params


# ---------------------------------------------------------------------------
# HF weight conversion (layout contract with DeepseekV2ForCausalLM)
# ---------------------------------------------------------------------------

_ATTN = "model.layers.{}.self_attn."
_MLP = "model.layers.{}.mlp."
_ATTN_MAP: dict[str, tuple[str, bool]] = {
    "attn_norm": ("model.layers.{}.input_layernorm.weight", False),
    "wq": (_ATTN + "q_proj.weight", True),
    "w_kva": (_ATTN + "kv_a_proj_with_mqa.weight", True),
    "kv_norm": (_ATTN + "kv_a_layernorm.weight", False),
    "w_kvb": (_ATTN + "kv_b_proj.weight", True),
    "wo": (_ATTN + "o_proj.weight", True),
    "mlp_norm": ("model.layers.{}.post_attention_layernorm.weight", False),
}
DENSE_HF_MAP = {
    **_ATTN_MAP,
    "w_gate": (_MLP + "gate_proj.weight", True),
    "w_up": (_MLP + "up_proj.weight", True),
    "w_down": (_MLP + "down_proj.weight", True),
}
ROUTED_HF_MAP = {
    **_ATTN_MAP,
    "router": (_MLP + "gate.weight", True),
    "we_gate": (_MLP + "experts.{}.gate_proj.weight", True),
    "we_up": (_MLP + "experts.{}.up_proj.weight", True),
    "we_down": (_MLP + "experts.{}.down_proj.weight", True),
    "ws_gate": (_MLP + "shared_experts.gate_proj.weight", True),
    "ws_up": (_MLP + "shared_experts.up_proj.weight", True),
    "ws_down": (_MLP + "shared_experts.down_proj.weight", True),
}


def hf_map(cfg: ModelConfig) -> dict[str, tuple[str, bool]]:
    """Leaf → (published tensor name, transpose?) of a routed layer; the
    leading dense layers' is DENSE_HF_MAP."""
    return {k: v for k, v in ROUTED_HF_MAP.items()
            if cfg.num_shared_experts or not k.startswith("ws_")}


def _rope_pairing(cfg: ModelConfig, n_heads: int, head: int, lead: int):
    """Column permutation that turns the published interleaved RoPE pairs
    (2j, 2j + 1) of each head's last `qk_rope_head_dim` columns into this
    program's split halves (j, j + dr/2): a head is `head` columns of
    which the first `lead` are not rotated."""
    import numpy as np

    dr = cfg.qk_rope_head_dim
    one = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    cols = [np.concatenate([np.arange(lead), lead + one]) + i * head
            for i in range(n_heads)]
    return np.concatenate(cols)


def from_getter(cfg: ModelConfig, get, dtype=jnp.bfloat16, place=None) -> Params:
    """The pytree from `get(published tensor name) -> host array`
    (engine/loader.py's safetensors reader, or a state dict): the two
    stacked trees, the rotated columns of `q_proj` and
    `kv_a_proj_with_mqa` re-paired (`_rope_pairing`). `place(path, array)`
    puts a finished leaf on the device (its sharding, its dtype)."""
    import numpy as np

    if place is None:
        def place(path, arr):
            return jnp.asarray(arr, dtype)

    kd = cfg.first_k_dense
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    perm = {
        "wq": _rope_pairing(cfg, cfg.num_heads, dn + dr, dn),
        "w_kva": _rope_pairing(cfg, 1, cfg.kv_lora_rank + dr,
                               cfg.kv_lora_rank),
    }

    def block(tree: str, name_map, layers):
        out = {}
        for leaf, (tmpl, tr) in name_map.items():
            def one(i):
                if tmpl.count("{}") == 2:
                    w = np.stack([np.asarray(get(tmpl.format(i, x))).T if tr
                                  else np.asarray(get(tmpl.format(i, x)))
                                  for x in range(cfg.num_experts)])
                else:
                    w = np.asarray(get(tmpl.format(i)))
                    w = w.T if tr else w
                return w[..., perm[leaf]] if leaf in perm else w
            out[leaf] = place((tree, leaf), np.stack([one(i) for i in layers]))
        return out

    params: Params = {
        "embed": place(("embed",), np.asarray(get("model.embed_tokens.weight"))),
        "dense": block("dense", DENSE_HF_MAP, range(kd)),
        "layers": block("layers", hf_map(cfg), range(kd, cfg.num_layers)),
        "final_norm": place(("final_norm",), np.asarray(get("model.norm.weight"))),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = place(
            ("lm_head",), np.asarray(get("lm_head.weight")).T)
    return params


def convert_hf_state_dict(cfg: ModelConfig, sd: dict[str, Any],
                          dtype=jnp.bfloat16) -> Params:
    """HF `DeepseekV2ForCausalLM.state_dict()` → our pytree."""
    import numpy as np

    def get(name):
        t = sd[name]
        if hasattr(t, "detach"):
            t = t.detach().to("cpu").float().numpy()
        return np.asarray(t)

    return from_getter(cfg, get, dtype)
