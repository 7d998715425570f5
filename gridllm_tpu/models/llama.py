"""Llama-family decoder (Llama 3/3.1/3.2, and the dense core Mixtral shares).

TPU-first design choices (SURVEY.md §7 step 4):
- Params are a plain pytree with per-layer weights STACKED on a leading [L]
  axis and the layer loop is `lax.scan` — one traced layer body, O(1)
  compile time in depth, and XLA donates the KV pool buffers through the
  scan so cache updates are in-place in HBM.
- ONE layer body (`_layer`) and ONE scan (`stack`); a phase is an `attend`
  closure over them, all static-shape. An engine launches `hidden_states`
  (/api/embed), `decode_step`, `verify_step` and `mixed_step`, which admits
  every prompt; `prefill` and, behind a prefix-cache hit, `prefill_chunk`
  only under `sp` (ring attention) and, through parallel/pipeline.py, under
  `pp`. `forward` is the golden tests' oracle and the graft entry.
- No data-dependent Python control flow anywhere; active/inactive slots are
  masked, not branched.

The reference has no model code to mirror (compute delegated to Ollama,
client/src/services/OllamaService.ts:17-27); HF Llama is the weight-layout
contract (see convert_hf_state_dict).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from gridllm_tpu.models.configs import ModelConfig
from gridllm_tpu.ops.attention import (
    attention_prefill,
    ragged_paged_attention,
)
from gridllm_tpu.ops.kvcache import (
    PagedKVCache,
    write_decode_all,
    write_multi_all,
    write_prefill_all,
)
from gridllm_tpu.ops.quant import qdot
from gridllm_tpu.ops.layers import apply_rope, precompute_rope, rms_norm

Params = dict[str, Any]

# Per-layer FFN body: (layer params, normed activations) -> FFN output.
# llama uses the dense SwiGLU `_mlp`; models/mixtral.py routes its sparse
# MoE body through the same decoder skeleton (attention/norm/paged-cache
# structure is identical across both families).
# A routed family's hook (cfg.num_experts) is called through `_ffn` with
# the router's input as well and returns (output, per-layer statistics).
MlpFn = Callable[["Params", jnp.ndarray], jnp.ndarray]

# Whole-prompt attention in place of the ops.attention dispatch: (q, k, v,
# seq_lens) -> attended values. The engine passes ops.ring_attention for
# sp-sharded long-context prefill.
AttnFn = Callable[
    [jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray
]


def _layer_kinds(cfg: ModelConfig, n: int):
    """Scan operands of a family whose layers differ in kind
    (cfg.window_layout / cfg.rope_layout): each layer's window and its
    RoPE switch, [n] int32 each. None where every layer is alike: the
    uniform families trace the static window and the unconditional RoPE
    they always have."""
    if not (cfg.window_layout or cfg.rope_layout):
        return None
    if n != cfg.num_layers:
        raise NotImplementedError(
            f"{cfg.name}: a block of {n} of {cfg.num_layers} patterned "
            "layers (pp stages) does not know its place in the pattern")
    rope = cfg.rope_layout or (1,) * n
    return (jnp.asarray(cfg.layer_windows, jnp.int32),
            jnp.asarray(rope, jnp.int32))


def _kind(cfg: ModelConfig, kind, pos: jnp.ndarray):
    """(window, RoPE positions) of one layer. A patterned family's window
    is the layer's traced scalar and its positions are multiplied by the
    layer's switch: at 0 every angle is 0, cos 1 and sin 0, so RoPE is
    the identity exactly (NoPE)."""
    if kind is None:
        return cfg.sliding_window, pos
    win, rope = kind
    return win, pos * rope


def _ffn(cfg: ModelConfig, mlp: MlpFn, lp: Params, hx: jnp.ndarray,
         pre: jnp.ndarray):
    """The feed-forward hook on the post-attention normed state `hx` →
    (output, per-layer statistics or None). A routed family's hook is also
    handed the router's input: `pre`, the PRE-attention normed state,
    where cfg.router_pre_attn says so."""
    if not cfg.num_experts:
        return mlp(lp, hx), None
    return mlp(lp, hx, pre if cfg.router_pre_attn else hx)


def _precision(x: jnp.ndarray):
    # fp32 runs (goldens) need exact matmuls; bf16 uses the MXU default.
    return jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None


def _check_supported(cfg: ModelConfig) -> None:
    # Loud failure beats silently-wrong attention for knobs this skeleton
    # doesn't route (gemma2 owns softcapping in models/gemma.py; uniform
    # sliding windows — mistral-v0.1-class — thread through the attention
    # calls here).
    if cfg.attn_logit_softcap:
        raise NotImplementedError(f"{cfg.name}: attn_logit_softcap")


def validate_mesh(cfg: ModelConfig, mesh) -> None:
    """Engine-init mesh check: ring-attention (sp) prefill has no
    sliding-window variant."""
    if cfg.sliding_window and mesh is not None and mesh.shape.get("sp", 1) > 1:
        raise ValueError(
            f"{cfg.name}: sliding-window attention cannot combine with sp "
            "(ring-attention prefill) yet — shape the mesh without sp"
        )


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16, dense_ffn: bool = True
) -> Params:
    """Random-init params (tests + synthetic bench; real loads go through
    engine/loader.py). `dense_ffn=False` skips the SwiGLU leaves — the MoE
    family reuses the attention skeleton and supplies its own expert leaves
    (materializing dense FFNs only to delete them would transiently cost
    ~11 GB at 8x7b scale)."""
    e, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, kvh, d, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_, cfg.num_layers
    ks = iter(jax.random.split(key, 16))

    def w(k, *shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5)
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    params: Params = {
        "embed": w(next(ks), v, e, scale=0.02),
        "layers": {
            "attn_norm": jnp.ones((L, e), dtype),
            "wq": w(next(ks), L, e, h * d),
            "wk": w(next(ks), L, e, kvh * d),
            "wv": w(next(ks), L, e, kvh * d),
            "wo": w(next(ks), L, h * d, e),
            "mlp_norm": jnp.ones((L, e), dtype),
        },
        "final_norm": jnp.ones((e,), dtype),
    }
    if dense_ffn:
        params["layers"]["w_gate"] = w(next(ks), L, e, f)
        params["layers"]["w_up"] = w(next(ks), L, e, f)
        params["layers"]["w_down"] = w(next(ks), L, f, e)
    if cfg.attn_bias:
        params["layers"]["bq"] = w(next(ks), L, h * d, scale=0.02)
        params["layers"]["bk"] = w(next(ks), L, kvh * d, scale=0.02)
        params["layers"]["bv"] = w(next(ks), L, kvh * d, scale=0.02)
    if cfg.qk_norm:
        params["layers"]["q_norm"] = jnp.ones((L, d), dtype)
        params["layers"]["k_norm"] = jnp.ones((L, d), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = w(next(ks), e, v, scale=0.02)
    return params


def _mlp(lp: Params, x: jnp.ndarray) -> jnp.ndarray:
    p = _precision(x)
    gate = qdot(x, lp["w_gate"], precision=p)
    up = qdot(x, lp["w_up"], precision=p)
    return qdot(jax.nn.silu(gate) * up, lp["w_down"], precision=p)


def _qkv(cfg: ModelConfig, lp: Params, x: jnp.ndarray):
    """x: [..., T, E] → q [..., T, H, D], k/v [..., T, KVH, D].

    Family knobs: qwen2 adds bias on the q/k/v projections (never on wo);
    qwen3 RMS-normalizes q/k per head over head_dim before rope (HF
    Qwen3Attention order: project → view heads → q_norm/k_norm → rope).
    """
    p = _precision(x)
    d = cfg.head_dim_
    q = qdot(x, lp["wq"], precision=p)
    k = qdot(x, lp["wk"], precision=p)
    v = qdot(x, lp["wv"], precision=p)
    if cfg.attn_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    q = q.reshape(*x.shape[:-1], cfg.num_heads, d)
    k = k.reshape(*x.shape[:-1], cfg.num_kv_heads, d)
    v = v.reshape(*x.shape[:-1], cfg.num_kv_heads, d)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
    return q, k, v


def _unembed(cfg: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return qdot(
        x, head, precision=_precision(x), preferred_element_type=jnp.float32
    )


def _seq_constraint(mesh) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """T-axis activation constraint for sp-sharded prefill: without pinning
    the [1, T, E] residual stream to P(None, "sp", None), whether the
    projections and MLP activations outside ring_attention's shard_map are
    O(T/sp) per device depends on GSPMD propagation luck (round-1 VERDICT
    #9; asserted structurally by tests/test_parallel.py)."""
    if mesh is None or mesh.shape.get("sp", 1) <= 1:
        return lambda x: x
    from jax.sharding import NamedSharding, PartitionSpec

    s = NamedSharding(mesh, PartitionSpec(None, "sp", None))
    return lambda x: jax.lax.with_sharding_constraint(x, s)


# One layer's attention, which is all a phase is: (layer index, the layer's
# window, roped q [..., T, H, D], roped k and v [..., T, KVH, D]) -> attended
# values, in any shape that flattens to [..., T, H*D].
Attend = Callable[..., jnp.ndarray]


def _layer(cfg: ModelConfig, lp: Params, li, kind, x, pos, inv_freq,
           attend: Attend, mlp: MlpFn, seq_c):
    """One decoder layer on x [..., T, E] at positions pos [..., T] → (x,
    the layer's roped k and v [..., T, KVH, D], the feed-forward's
    statistics)."""
    win, lpos = _kind(cfg, kind, pos)
    pre = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q, k, v = _qkv(cfg, lp, pre)
    q = apply_rope(q, lpos, inv_freq)
    k = apply_rope(k, lpos, inv_freq)
    att = attend(li, win, q, k, v).reshape(*x.shape[:-1], -1)
    x = seq_c(x + qdot(att, lp["wo"], precision=_precision(x)))
    hx = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    y, stats = _ffn(cfg, mlp, lp, hx, pre)
    return seq_c(x + y), k, v, stats


def stack(layers: Params, cfg: ModelConfig, x: jnp.ndarray, pos: jnp.ndarray,
          attend: Attend, mlp: MlpFn = _mlp, seq_c=lambda x: x,
          kv: bool = True):
    """The ONE layer scan every entry point shares, over an arbitrary
    stacked block of layers: the full [L] stack, or a pp stage's block
    with its matching pool block inside `attend` (parallel/pipeline.py).

    x: [..., T, E]; pos: [..., T] absolute positions. Returns (x out, k_new
    [N, ..., T, KVH, D], v_new, stats): K/V ride out as scan ys and the
    pool is written ONCE after the scan by the caller (per-layer writes
    inside the scan defeat XLA's in-place aliasing and cost full-pool
    copies — round-4 profiling); `stats` are a routed family's per-layer
    statistics [N, ...] (`_ffn`), None for a dense one. `kv=False` stacks
    nothing (hidden_states has no cache to fill)."""
    _check_supported(cfg)
    inv_freq = precompute_rope(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
    n = jax.tree.leaves(layers)[0].shape[0]

    def body(x, xs):
        lp, li, kind = xs
        if cfg.num_experts:
            # a routed family's grouped kernel takes the stack and an index:
            # handed this scan's slice of the experts it would be handed a copy
            lp = {**lp, "layer_stack": (layers, li)}
        x, *ys = _layer(cfg, lp, li, kind, x, pos, inv_freq, attend, mlp,
                        seq_c)
        return x, tuple(ys) if kv else None

    x, ys = jax.lax.scan(
        body, x, (layers, jnp.arange(n, dtype=jnp.int32),
                  _layer_kinds(cfg, n)))
    return (x, *ys) if kv else (x, None, None, None)


def whole_attend(cfg: ModelConfig, seq_lens, attn: AttnFn | None = None,
                 mesh=None) -> Attend:
    """Whole-prompt attention, no cache read (hidden_states, prefill): the
    ops.attention dispatch (jnp ref / Pallas flash), or `attn`."""
    patterned = bool(cfg.window_layout or cfg.rope_layout)

    def attend(li, win, q, k, v):
        if attn is None:
            return attention_prefill(
                q, k, v, seq_lens, use_pallas=cfg.use_pallas, window=win,
                mesh=mesh)
        # a uniform family's layers never pass the window (nor could they
        # to ring attention's AttnFn)
        if patterned:
            return attn(q, k, v, seq_lens, window=win)
        return attn(q, k, v, seq_lens)

    return attend


def _pool_read(cfg: ModelConfig, k_pool, v_pool, page_size: int, mesh):
    """ragged_paged_attention against the page pool, which holds the
    PREFIX only: the launch's own K/V are overlaid in-register by the
    attention. The FULL pool rides in as a scan closure with `li` selecting
    the layer — per-layer xs slices would materialize 2×pool-slice
    copies/iter."""

    def read(li, win, **regions):
        return ragged_paged_attention(
            k_pool, v_pool, page_size, layer=li, use_pallas=cfg.use_pallas,
            window=win, mesh=mesh, **regions)

    return read


def chunk_attend(cfg: ModelConfig, k_pool, v_pool, page_size: int, row,
                 start, total, mesh=None) -> Attend:
    """The ragged chunk region: x [1, C, E] is ONE slot's prefill chunk at
    positions start.. behind its cached prefix on pages `row` (paged-prefix
    streaming flash when kernels are on)."""
    read = _pool_read(cfg, k_pool, v_pool, page_size, mesh)

    def attend(li, win, q, k, v):
        return read(li, win, q_chunk=q, chunk_row=row, chunk_start=start,
                    chunk_total=total, k_chunk=k[0], v_chunk=v[0])[0]

    return attend


def group_attend(cfg: ModelConfig, k_pool, v_pool, page_size: int,
                 page_table, lengths, mesh=None, tree_pos=None,
                 tree_mask=None) -> Attend:
    """The ragged group region, ONE launch over all slots: x [S, Td, E] is
    Td rows a slot behind each slot's `lengths` cached rows — a speculative
    verify at Td = K+1, where `tree_pos` / `tree_mask` make the rows a
    token tree (verify_step); a decode step is Td = 1 and keeps its x
    [S, E], the unit axis is the launch's alone."""
    read = _pool_read(cfg, k_pool, v_pool, page_size, mesh)

    def attend(li, win, q, k, v):
        if q.ndim == 3:
            q, k, v = q[:, None], k[:, None], v[:, None]
        return read(li, win, q_group=q, page_table=page_table,
                    group_lengths=lengths, k_group=k, v_group=v,
                    tree_pos=tree_pos, tree_mask=tree_mask)[1]

    return attend


def hidden_states(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    mlp: MlpFn = _mlp,
    seq_lens: jnp.ndarray | None = None,
    attn: AttnFn | None = None,
    embeds: jnp.ndarray | None = None,
    mesh=None,
) -> jnp.ndarray:
    """Final-norm hidden states [B, T, E] (embeddings path; no unembed).
    seq_lens masks padding keys out of attention (None → all valid).
    `embeds` ([B, T, E]) overrides the embedding lookup (vision splice)."""
    b, t = tokens.shape
    x = params["embed"][tokens] if embeds is None else embeds.astype(
        params["embed"].dtype
    )
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    if seq_lens is None:
        seq_lens = jnp.full((b,), t, jnp.int32)
    x, _, _, _ = stack(params["layers"], cfg, x, pos,
                       whole_attend(cfg, seq_lens, attn, mesh), mlp, kv=False)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def forward(
    params: Params, cfg: ModelConfig, tokens: jnp.ndarray, mlp: MlpFn = _mlp,
    embeds: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Cache-free full forward: tokens [B, T] → logits [B, T, V] (fp32).

    The oracle path — golden tests compare this against HF; prefill/decode
    must agree with it (tested in tests/test_models.py).
    """
    return _unembed(
        cfg, params, hidden_states(params, cfg, tokens, mlp, embeds=embeds)
    )


def _admit(params, cfg, tokens, start, length, cache, slot, table_row,
           attend: Attend, mlp, mesh, embeds, seq_c=lambda x: x):
    """ONE slot's rows `tokens` [T] (padded; `length` valid) at positions
    start.. through `attend` → (the last *valid* token's logits [V] fp32,
    the cache with the rows written on `table_row`'s pages and
    lengths[slot] = start + length)."""
    x = params["embed"][tokens] if embeds is None else embeds
    x = seq_c(x.astype(params["embed"].dtype)[None])  # [1, T, E]
    pos = (start + jnp.arange(tokens.shape[0], dtype=jnp.int32))[None]
    x, k_new, v_new, _ = stack(
        params["layers"], cfg, x, pos, attend, mlp, seq_c)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = _unembed(cfg, params, x[0, jnp.maximum(length - 1, 0)])
    k_pool, v_pool = write_prefill_all(
        cache.k, cache.v, k_new[:, 0], v_new[:, 0], table_row, start, length,
        cache.page_size, use_pallas=cfg.use_pallas, mesh=mesh,
    )
    return logits, PagedKVCache(
        k=k_pool, v=v_pool,
        page_table=cache.page_table.at[slot].set(table_row),
        lengths=cache.lengths.at[slot].set(start + length),
        page_size=cache.page_size,
    )


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    length: jnp.ndarray,
    cache: PagedKVCache,
    slot: jnp.ndarray,
    table_row: jnp.ndarray,
    mlp: MlpFn = _mlp,
    attn: AttnFn | None = None,
    mesh=None,
    embeds: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """Prefill ONE slot. tokens: [T] (padded bucket), length: scalar valid
    count, table_row: [max_pages] this slot's pages. Returns (last-token
    logits [V] fp32, updated cache). Sets cache.lengths[slot] = length.
    `mesh` (with sp > 1) pins the residual stream's T axis to the sp mesh
    axis so prefill activations really are O(T/sp) per device.
    `embeds` ([T, E]) overrides the token-embedding lookup — the vision
    path (models/llava.py splice_embeds) feeds image-spliced embeddings;
    tokens are still used for lengths/window bookkeeping by the caller.
    """
    return _admit(
        params, cfg, tokens, jnp.int32(0), length, cache, slot, table_row,
        whole_attend(cfg, length[None], attn, mesh), mlp, mesh, embeds,
        _seq_constraint(mesh),
    )


def prefill_chunk(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    start: jnp.ndarray,
    length: jnp.ndarray,
    cache: PagedKVCache,
    slot: jnp.ndarray,
    table_row: jnp.ndarray,
    mlp: MlpFn = _mlp,
    mesh=None,  # accepted for family-API uniformity (MoE uses it)
    embeds: jnp.ndarray | None = None,  # [C, E] override (vision splice)
) -> tuple[jnp.ndarray, PagedKVCache]:
    """Prefill ONE CHUNK of one slot against its cached prefix.

    tokens: [C] (padded chunk bucket), start: scalar absolute position of
    tokens[0] (0 for the first chunk), length: scalar valid tokens in THIS
    chunk. Attention reads prefix K/V from the page pool (the chunk's K/V
    are overlaid inside it), so a long prompt runs as ceil(T/C) invocations
    of ONE compiled program instead of a per-length trace (VERDICT.md #4).
    Returns (last-valid-token logits [V] fp32, cache with lengths[slot] =
    start + length).
    """
    return _admit(
        params, cfg, tokens, start, length, cache, slot, table_row,
        chunk_attend(cfg, cache.k, cache.v, cache.page_size, table_row,
                     start, start + length, mesh), mlp, mesh, embeds,
    )


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    cache: PagedKVCache,
    active: jnp.ndarray,
    mlp: MlpFn = _mlp,
    mesh=None,  # meshed-kernel dispatch (ops) + MoE EP routing
    with_stats: bool = False,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """One decode step for ALL slots. tokens: [S] (last sampled token per
    slot), active: [S] bool. Returns (logits [S, V] fp32, updated cache
    with lengths advanced for active slots) and, `with_stats`, a routed
    family's statistics summed over the layers.
    """
    x = params["embed"][tokens]  # [S, E]
    positions = cache.lengths  # new token's position per slot
    # clamp at pool-wide capacity: finished slots stay device-active for up
    # to decode_block × pipeline_depth in-flight steps after the host
    # finishes them (engine.py); unbounded growth would walk the length
    # past the page table (reads) even though writes are sentinel-dropped
    new_lengths = jnp.minimum(
        cache.lengths + active.astype(jnp.int32), cache.max_context
    )

    x, k_new, v_new, stats = stack(
        params["layers"], cfg, x, positions,
        group_attend(cfg, cache.k, cache.v, cache.page_size,
                     cache.page_table, positions, mesh), mlp,
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = _unembed(cfg, params, x)

    k_pool, v_pool = write_decode_all(
        cache.k, cache.v, k_new, v_new, cache.page_table, positions, active,
        cache.page_size, use_pallas=cfg.use_pallas, mesh=mesh,
    )
    cache = PagedKVCache(
        k=k_pool, v=v_pool, page_table=cache.page_table,
        lengths=new_lengths, page_size=cache.page_size,
    )
    if with_stats:
        return logits, cache, stats.sum(axis=0)
    return logits, cache


def verify_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    cache: PagedKVCache,
    active: jnp.ndarray,
    mlp: MlpFn = _mlp,
    mesh=None,
    tree_pos: jnp.ndarray | None = None,
    tree_mask: jnp.ndarray | None = None,
    with_stats: bool = False,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """One speculative-verify forward for ALL slots (ISSUE 5). tokens:
    [S, T] candidate blocks (col 0 = each slot's committed last token,
    cols 1..T-1 = drafted candidates), active: [S] bool. Returns (logits
    [S, T, V] fp32 — row j is the distribution after consuming candidates
    0..j — and the cache with the candidates' KV written OPTIMISTICALLY at
    positions lengths[s]..lengths[s]+T-1 but lengths UNCHANGED: the engine
    commits the accepted length afterwards via
    ops.kvcache.rollback_to_length, which drops rejected rows).

    Tree verify (ISSUE 18): with `tree_pos` ([T] node depths) and
    `tree_mask` ([T, T] ancestor-or-self, both static host constants) cols
    1..T-1 are a token TREE — node i takes rope at LOGICAL position
    lengths[s] + tree_pos[i] and its query attends the prefix plus exactly
    its tree ancestors (ops.attention.paged_attention_verify_ref), but
    still lands at STORAGE position lengths[s] + i (the engine compacts
    the accepted path with ops.kvcache.commit_tree_path before rolling
    lengths forward); logits row i is the distribution after consuming
    node i's root path. `with_stats`: as decode_step."""
    s, t = tokens.shape
    x = params["embed"][tokens]  # [S, T, E]
    base = cache.lengths
    positions = base[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    rel = (jnp.asarray(tree_pos, jnp.int32) if tree_pos is not None
           else jnp.arange(t, dtype=jnp.int32))

    x, k_new, v_new, stats = stack(
        params["layers"], cfg, x, base[:, None] + rel[None],
        group_attend(cfg, cache.k, cache.v, cache.page_size,
                     cache.page_table, base, mesh, tree_pos, tree_mask), mlp,
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = _unembed(cfg, params, x)  # [S, T, V]

    k_pool, v_pool = write_multi_all(
        cache.k, cache.v, k_new, v_new, cache.page_table, positions, active,
        cache.page_size, use_pallas=cfg.use_pallas, mesh=mesh,
    )
    cache = PagedKVCache(
        k=k_pool, v=v_pool, page_table=cache.page_table,
        lengths=base, page_size=cache.page_size,
    )
    if with_stats:
        return logits, cache, stats.sum(axis=0)
    return logits, cache


def mixed_step(
    params: Params,
    cfg: ModelConfig,
    chunk_tokens: jnp.ndarray,
    chunk_start: jnp.ndarray,
    chunk_len: jnp.ndarray,
    slot: jnp.ndarray,
    table_row: jnp.ndarray,
    tokens: jnp.ndarray,
    cache: PagedKVCache,
    active: jnp.ndarray,
    mlp: MlpFn = _mlp,
    mesh=None,
    embeds: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, PagedKVCache]:
    """One fused chunked-prefill + decode step: the prefill
    chunk for ONE admitting slot PLUS one decode token for every active
    slot, batched into one ragged descriptor — a single attention launch
    per layer.
    Long prefills stop stalling running streams: the batch keeps decoding
    while the chunk prefills alongside it (the DeepServe mixed-step
    shape).

    chunk_tokens: [C] (padded chunk), chunk_start/chunk_len: scalars,
    table_row: [max_pages] the admitting slot's pages, tokens: [S] each
    slot's last token, active: [S]. Returns (chunk last-valid-token
    logits [V], decode logits [S, V], updated cache with the chunk
    written at [chunk_start, chunk_start+chunk_len) and active slots
    advanced by one).

    The ragged token batch is x [1, C+S, E]: rows [0, C) the chunk at
    positions chunk_start + i, rows [C, C+S) one decode token per slot at
    positions lengths[s]. Pointwise sublayers (norms, projections, MLP)
    are row-independent, so each region's rows compute exactly what the
    separate per-phase programs would."""
    c = chunk_tokens.shape[0]
    xc = params["embed"][chunk_tokens] if embeds is None else embeds
    xg = params["embed"][tokens]
    x = jnp.concatenate([
        xc.astype(params["embed"].dtype), xg.astype(params["embed"].dtype)
    ])[None]                                        # [1, C+S, E]
    positions = cache.lengths
    total = chunk_start + chunk_len
    pos = jnp.concatenate([
        chunk_start + jnp.arange(c, dtype=jnp.int32), positions
    ])[None]
    read = _pool_read(cfg, cache.k, cache.v, cache.page_size, mesh)

    def attend(li, win, q, k, v):
        # both regions in ONE ragged launch
        oc, og = read(
            li, win,
            q_chunk=q[:, :c], chunk_row=table_row, chunk_start=chunk_start,
            chunk_total=total, k_chunk=k[0, :c], v_chunk=v[0, :c],
            q_group=q[0, c:][:, None], page_table=cache.page_table,
            group_lengths=positions, k_group=k[0, c:][:, None],
            v_group=v[0, c:][:, None])
        return jnp.concatenate([oc[0], og[:, 0]])

    x, k_new, v_new, _ = stack(params["layers"], cfg, x, pos, attend, mlp)
    k_new, v_new = k_new[:, 0], v_new[:, 0]  # [L, C+S, KVH, D]
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    chunk_logits = _unembed(
        cfg, params, x[0, jnp.maximum(chunk_len - 1, 0)]
    )
    dec_logits = _unembed(cfg, params, x[0, c:])

    # region writes target disjoint pages (the admitting slot is not yet
    # active), so the order is immaterial
    k_pool, v_pool = write_prefill_all(
        cache.k, cache.v, k_new[:, :c], v_new[:, :c], table_row,
        chunk_start, chunk_len, cache.page_size, use_pallas=cfg.use_pallas,
        mesh=mesh,
    )
    k_pool, v_pool = write_decode_all(
        k_pool, v_pool, k_new[:, c:], v_new[:, c:], cache.page_table,
        positions, active, cache.page_size, use_pallas=cfg.use_pallas,
        mesh=mesh,
    )
    new_lengths = jnp.minimum(
        cache.lengths + active.astype(jnp.int32), cache.max_context
    ).at[slot].set(total)
    cache = PagedKVCache(
        k=k_pool, v=v_pool,
        page_table=cache.page_table.at[slot].set(table_row),
        lengths=new_lengths, page_size=cache.page_size,
    )
    return chunk_logits, dec_logits, cache


# ---------------------------------------------------------------------------
# HF weight conversion (layout contract with transformers LlamaForCausalLM)
# ---------------------------------------------------------------------------

# Single source of truth for the HF<->ours layout contract: our layer-leaf
# name → (HF tensor name template, transpose?). {} is the layer index (an
# extra {} is the expert index for MoE leaves). engine/loader.py drives the
# safetensors path off this same table. HF stores projections [out, in];
# we keep [in, out] so forward is x @ W — hence transpose=True on matmuls.
HF_MAP: dict[str, tuple[str, bool]] = {
    "attn_norm": ("model.layers.{}.input_layernorm.weight", False),
    "wq": ("model.layers.{}.self_attn.q_proj.weight", True),
    "wk": ("model.layers.{}.self_attn.k_proj.weight", True),
    "wv": ("model.layers.{}.self_attn.v_proj.weight", True),
    "wo": ("model.layers.{}.self_attn.o_proj.weight", True),
    "mlp_norm": ("model.layers.{}.post_attention_layernorm.weight", False),
    "w_gate": ("model.layers.{}.mlp.gate_proj.weight", True),
    "w_up": ("model.layers.{}.mlp.up_proj.weight", True),
    "w_down": ("model.layers.{}.mlp.down_proj.weight", True),
}


def hf_map(cfg: ModelConfig) -> dict[str, tuple[str, bool]]:
    """HF_MAP extended with the config's family knobs (qwen2 qkv bias,
    qwen3 qk norms) — the full layout contract for llama-skeleton models."""
    m = dict(HF_MAP)
    if cfg.attn_bias:
        m["bq"] = ("model.layers.{}.self_attn.q_proj.bias", False)
        m["bk"] = ("model.layers.{}.self_attn.k_proj.bias", False)
        m["bv"] = ("model.layers.{}.self_attn.v_proj.bias", False)
    if cfg.qk_norm:
        m["q_norm"] = ("model.layers.{}.self_attn.q_norm.weight", False)
        m["k_norm"] = ("model.layers.{}.self_attn.k_norm.weight", False)
    return m


def convert_state_dict(
    cfg: ModelConfig,
    sd: dict[str, Any],
    name_map: dict[str, tuple[str, bool]],
    dtype=jnp.bfloat16,
) -> Params:
    """Generic HF state_dict → stacked-layer pytree, driven by a name map
    (llama's HF_MAP or mixtral's). Accepts numpy/torch tensors."""
    import numpy as np

    from gridllm_tpu.models import hf_layout

    def get(name):
        t = sd[name]
        if hasattr(t, "detach"):
            t = t.detach().to("cpu").float().numpy()
        return np.asarray(t)

    return hf_layout.to_pytree(cfg, get, name_map, dtype)


def convert_hf_state_dict(cfg: ModelConfig, sd: dict[str, Any], dtype=jnp.bfloat16) -> Params:
    """HF `LlamaForCausalLM.state_dict()`-style mapping → our pytree
    (also Qwen2/Qwen3ForCausalLM — same skeleton, knobs via hf_map)."""
    return convert_state_dict(cfg, sd, hf_map(cfg), dtype)
