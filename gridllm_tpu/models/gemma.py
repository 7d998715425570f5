"""Gemma-2 family decoder (gemma2:2b/9b/27b).

Same scan-stacked/paged-cache skeleton as models/llama.py, but the gemma2
block differs in every place that matters for numerics, so the family owns
its layer body instead of parameterizing llama's:

- RMSNorm multiplies by (1 + w), in fp32 (HF Gemma2RMSNorm);
- FOUR norms per layer: pre/post attention and pre/post feed-forward,
  with the post-norms applied to the sublayer OUTPUT before the residual;
- GeGLU with tanh-approximated gelu (hidden_activation
  "gelu_pytorch_tanh");
- embeddings scaled by sqrt(hidden_size) (cast to the activation dtype
  first, matching HF's normalizer rounding);
- attention logits tanh-softcapped (attn_logit_softcapping) and scaled by
  query_pre_attn_scalar**-0.5 instead of head_dim**-0.5 — implemented by
  pre-scaling q with sqrt(d / qpas) so the shared attention ops keep
  their 1/sqrt(d) convention;
- sliding-window attention on EVEN layers (HF: layer_idx % 2 == 0),
  threaded through the scan as a per-layer window scalar — handled by
  both the ops/attention.py jnp paths and the Pallas kernels (softcap +
  window as traced per-layer scalars, tests/test_pallas.py); dispatch
  follows cfg.use_pallas;
- final logits tanh-softcapped (final_logit_softcapping).

Weight layout contract: HF Gemma2ForCausalLM (tied embeddings; the four
per-layer norms under their HF names). The reference served gemma via
Ollama passthrough (client/src/services/OllamaService.ts); no model code
to mirror.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from gridllm_tpu.models.configs import ModelConfig
# _precision: the families' shared dtype→matmul-precision policy;
# validate_mesh: gemma2 always has sliding windows, so llama's window×sp
# engine-init guard is exactly the needed rule — one copy, no drift
from gridllm_tpu.models.llama import _precision, validate_mesh  # noqa: F401
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
from gridllm_tpu.ops.layers import apply_rope, precompute_rope
from gridllm_tpu.ops.quant import qdot

Params = dict[str, Any]


def _gnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Gemma RMSNorm: fp32, multiplies by (1 + w)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dtype)




def _geglu(lp: Params, x: jnp.ndarray) -> jnp.ndarray:
    p = _precision(x)
    gate = qdot(x, lp["w_gate"], precision=p)
    up = qdot(x, lp["w_up"], precision=p)
    return qdot(
        jax.nn.gelu(gate, approximate=True) * up, lp["w_down"], precision=p
    )


def _embed_in(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
              embeds: jnp.ndarray | None = None) -> jnp.ndarray:
    x = params["embed"][tokens] if embeds is None else embeds
    x = x.astype(params["embed"].dtype)
    # HF casts the sqrt(E) normalizer to the hidden dtype BEFORE the
    # multiply — mirroring that rounding keeps bf16 goldens bit-tight
    return x * jnp.asarray(math.sqrt(cfg.hidden_size), x.dtype)


def _q_prescale(cfg: ModelConfig, q: jnp.ndarray) -> jnp.ndarray:
    """Make the ops' 1/sqrt(d) scale equal gemma's 1/sqrt(qpas)."""
    d = cfg.head_dim_
    qpas = cfg.query_pre_attn_scalar or d
    if qpas == d:
        return q
    return q * jnp.asarray(math.sqrt(d / qpas), q.dtype)


def _qkv(cfg: ModelConfig, lp: Params, x: jnp.ndarray):
    p = _precision(x)
    d = cfg.head_dim_
    q = qdot(x, lp["wq"], precision=p).reshape(*x.shape[:-1], cfg.num_heads, d)
    k = qdot(x, lp["wk"], precision=p).reshape(*x.shape[:-1], cfg.num_kv_heads, d)
    v = qdot(x, lp["wv"], precision=p).reshape(*x.shape[:-1], cfg.num_kv_heads, d)
    return q, k, v


def _layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer sliding window (0 = global): EVEN layers slide."""
    return jnp.asarray(cfg.layer_windows, jnp.int32)


def _unembed(cfg: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    logits = qdot(
        x, params["embed"].T, precision=_precision(x),
        preferred_element_type=jnp.float32,
    )
    cap = cfg.final_logit_softcap
    return cap * jnp.tanh(logits / cap) if cap else logits


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    e, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, kvh, d, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_, cfg.num_layers
    ks = iter(jax.random.split(key, 10))

    def w(k, *shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5)
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    return {
        "embed": w(next(ks), v, e, scale=0.02),
        "layers": {
            "attn_norm": jnp.zeros((L, e), dtype),      # (1+w) convention
            "wq": w(next(ks), L, e, h * d),
            "wk": w(next(ks), L, e, kvh * d),
            "wv": w(next(ks), L, e, kvh * d),
            "wo": w(next(ks), L, h * d, e),
            "post_attn_norm": jnp.zeros((L, e), dtype),
            "pre_ffn_norm": jnp.zeros((L, e), dtype),
            "w_gate": w(next(ks), L, e, f),
            "w_up": w(next(ks), L, e, f),
            "w_down": w(next(ks), L, f, e),
            "post_ffn_norm": jnp.zeros((L, e), dtype),
        },
        "final_norm": jnp.zeros((e,), dtype),
    }


def _block(cfg: ModelConfig, lp: Params, x: jnp.ndarray, attn_out: jnp.ndarray,
           ) -> jnp.ndarray:
    """Post-attention half of the gemma2 block: post-norm the attention
    output, add residual, then the normed GeGLU with its own post-norm."""
    eps = cfg.rms_eps
    x = x + _gnorm(attn_out, lp["post_attn_norm"], eps)
    h = _gnorm(x, lp["pre_ffn_norm"], eps)
    h = _geglu(lp, h)
    return x + _gnorm(h, lp["post_ffn_norm"], eps)


def _scan_layers(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                 pos: jnp.ndarray, attn_fn):
    """The ONE gemma2 layer scan all four entry points share.

    x: [B, T, E]; pos: [B, T] absolute positions;
    attn_fn(q, k, v, win, li) -> attended [B, T, H*D] (q/k post-rope,
    q pre-scaled; win = this layer's sliding window, li = layer index).
    Returns (x, k_ys [L, B, T, KVH, D], v_ys) — pool writes are the
    caller's.
    """
    inv_freq = precompute_rope(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
    windows = _layer_windows(cfg)

    def layer(x, xs):
        lp, win, li = xs
        hx = _gnorm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = _qkv(cfg, lp, hx)
        q = _q_prescale(cfg, apply_rope(q, pos, inv_freq))
        k = apply_rope(k, pos, inv_freq)
        att = qdot(attn_fn(q, k, v, win, li), lp["wo"],
                   precision=_precision(x))
        return _block(cfg, lp, x, att), (k, v)

    x, (k_ys, v_ys) = jax.lax.scan(
        layer, x,
        (params["layers"], windows,
         jnp.arange(cfg.num_layers, dtype=jnp.int32)),
    )
    return x, k_ys, v_ys


def hidden_states(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    mlp=None,  # family-API uniformity (gemma owns its GeGLU)
    seq_lens: jnp.ndarray | None = None,
    attn=None,
    embeds: jnp.ndarray | None = None,
    mesh=None,
) -> jnp.ndarray:
    del mlp, attn
    b, t = tokens.shape
    x = _embed_in(params, cfg, tokens, embeds)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    if seq_lens is None:
        seq_lens = jnp.full((b,), t, jnp.int32)

    def attn_fn(q, k, v, win, li):
        return attention_prefill(
            q, k, v, seq_lens, use_pallas=cfg.use_pallas,
            logit_softcap=cfg.attn_logit_softcap, window=win, mesh=mesh,
        ).reshape(b, t, -1)

    x, _, _ = _scan_layers(params, cfg, x, pos, attn_fn)
    return _gnorm(x, params["final_norm"], cfg.rms_eps)


def forward(
    params: Params, cfg: ModelConfig, tokens: jnp.ndarray, mlp=None,
    embeds: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Cache-free full forward: tokens [B, T] → logits [B, T, V] fp32
    (the golden-test oracle vs HF Gemma2ForCausalLM)."""
    return _unembed(
        cfg, params, hidden_states(params, cfg, tokens, embeds=embeds)
    )


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    length: jnp.ndarray,
    cache: PagedKVCache,
    slot: jnp.ndarray,
    table_row: jnp.ndarray,
    mlp=None,
    attn=None,
    mesh=None,
    embeds: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """Prefill ONE slot (same contract as llama.prefill)."""
    del mlp
    if attn is not None:
        raise NotImplementedError(
            f"{cfg.name}: custom prefill attention (sp ring) is not "
            "supported — validate_mesh rejects such meshes at engine init"
        )
    t = tokens.shape[0]
    x = _embed_in(params, cfg, tokens, embeds)[None]  # [1, T, E]
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    seq_lens = length[None]

    def attn_fn(q, k, v, win, li):
        return attention_prefill(
            q, k, v, seq_lens, use_pallas=cfg.use_pallas,
            logit_softcap=cfg.attn_logit_softcap, window=win, mesh=mesh,
        ).reshape(1, t, -1)

    x, k_ys, v_ys = _scan_layers(params, cfg, x, pos, attn_fn)
    k_new, v_new = k_ys[:, 0], v_ys[:, 0]  # [L, T, KVH, D]
    x = _gnorm(x, params["final_norm"], cfg.rms_eps)
    logits = _unembed(cfg, params, x[0, jnp.maximum(length - 1, 0)])

    k_pool, v_pool = write_prefill_all(
        cache.k, cache.v, k_new, v_new, table_row, jnp.int32(0), length,
        cache.page_size, use_pallas=cfg.use_pallas, mesh=mesh,
    )
    return logits, PagedKVCache(
        k=k_pool, v=v_pool,
        page_table=cache.page_table.at[slot].set(table_row),
        lengths=cache.lengths.at[slot].set(length),
        page_size=cache.page_size,
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
    mlp=None,
    mesh=None,
    embeds: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """Chunked prefill against the cached prefix (llama.prefill_chunk
    contract)."""
    del mlp
    t = tokens.shape[0]
    x = _embed_in(params, cfg, tokens, embeds)[None]  # [1, C, E]
    pos = (start + jnp.arange(t, dtype=jnp.int32))[None]
    total = start + length

    def attn_fn(q, k, v, win, li):
        att, _ = ragged_paged_attention(
            cache.k, cache.v, cache.page_size,
            q_chunk=q, chunk_row=table_row, chunk_start=start,
            chunk_total=total, k_chunk=k[0], v_chunk=v[0], layer=li,
            use_pallas=cfg.use_pallas,
            logit_softcap=cfg.attn_logit_softcap, window=win, mesh=mesh,
        )
        return att.reshape(1, t, -1)

    x, k_ys, v_ys = _scan_layers(params, cfg, x, pos, attn_fn)
    k_new, v_new = k_ys[:, 0], v_ys[:, 0]  # [L, C, KVH, D]
    x = _gnorm(x, params["final_norm"], cfg.rms_eps)
    logits = _unembed(cfg, params, x[0, jnp.maximum(length - 1, 0)])

    k_pool, v_pool = write_prefill_all(
        cache.k, cache.v, k_new, v_new, table_row, start, length,
        cache.page_size, use_pallas=cfg.use_pallas, mesh=mesh,
    )
    return logits, PagedKVCache(
        k=k_pool, v=v_pool,
        page_table=cache.page_table.at[slot].set(table_row),
        lengths=cache.lengths.at[slot].set(total),
        page_size=cache.page_size,
    )


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    cache: PagedKVCache,
    active: jnp.ndarray,
    mlp=None,
    mesh=None,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """One decode step for ALL slots (llama.decode_step contract)."""
    del mlp
    s = tokens.shape[0]
    # the decode token is a length-1 "sequence" per slot: [S, 1, E] with
    # per-slot positions, so the shared scan body applies unchanged
    x = _embed_in(params, cfg, tokens)[:, None]  # [S, 1, E]
    positions = cache.lengths
    new_lengths = jnp.minimum(
        cache.lengths + active.astype(jnp.int32), cache.max_context
    )

    def attn_fn(q, k, v, win, li):
        _, att = ragged_paged_attention(
            cache.k, cache.v, cache.page_size,
            q_group=q, page_table=cache.page_table,
            group_lengths=positions, k_group=k, v_group=v, layer=li,
            use_pallas=cfg.use_pallas,
            logit_softcap=cfg.attn_logit_softcap, window=win, mesh=mesh,
        )
        return att.reshape(s, 1, -1)

    x, k_ys, v_ys = _scan_layers(
        params, cfg, x, positions[:, None], attn_fn
    )
    k_new, v_new = k_ys[:, :, 0], v_ys[:, :, 0]  # [L, S, KVH, D]
    x = _gnorm(x[:, 0], params["final_norm"], cfg.rms_eps)
    logits = _unembed(cfg, params, x)

    k_pool, v_pool = write_decode_all(
        cache.k, cache.v, k_new, v_new, cache.page_table, positions, active,
        cache.page_size, use_pallas=cfg.use_pallas, mesh=mesh,
    )
    return logits, PagedKVCache(
        k=k_pool, v=v_pool, page_table=cache.page_table,
        lengths=new_lengths, page_size=cache.page_size,
    )


def verify_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    cache: PagedKVCache,
    active: jnp.ndarray,
    mlp=None,
    mesh=None,
    tree_pos: jnp.ndarray | None = None,
    tree_mask: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """Speculative-verify forward (llama.verify_step contract): T candidate
    tokens per slot in one pass, KV written optimistically, lengths left
    for the engine's rollback_to_length commit. Softcap and the per-layer
    sliding windows thread through the ragged group region exactly as they
    do through the decode path. Tree verify (`tree_pos`/`tree_mask`,
    ISSUE 18): rope at logical positions base + depth, KV still stored at
    base + i — same contract as llama.verify_step."""
    del mlp
    s, t = tokens.shape
    x = _embed_in(params, cfg, tokens)  # [S, T, E]
    base = cache.lengths
    store_pos = base[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    pos = (base[:, None] + jnp.asarray(tree_pos, jnp.int32)[None]
           if tree_pos is not None else store_pos)

    def attn_fn(q, k, v, win, li):
        _, att = ragged_paged_attention(
            cache.k, cache.v, cache.page_size,
            q_group=q, page_table=cache.page_table, group_lengths=base,
            k_group=k, v_group=v, layer=li, use_pallas=cfg.use_pallas,
            logit_softcap=cfg.attn_logit_softcap, window=win, mesh=mesh,
            tree_pos=tree_pos, tree_mask=tree_mask,
        )
        return att.reshape(s, t, -1)

    x, k_new, v_new = _scan_layers(params, cfg, x, pos, attn_fn)
    x = _gnorm(x, params["final_norm"], cfg.rms_eps)
    logits = _unembed(cfg, params, x)  # [S, T, V]

    k_pool, v_pool = write_multi_all(
        cache.k, cache.v, k_new, v_new, cache.page_table, store_pos, active,
        cache.page_size, use_pallas=cfg.use_pallas, mesh=mesh,
    )
    return logits, PagedKVCache(
        k=k_pool, v=v_pool, page_table=cache.page_table,
        lengths=base, page_size=cache.page_size,
    )


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
    mlp=None,
    mesh=None,
    embeds: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, PagedKVCache]:
    """Fused chunked-prefill + decode step (llama.mixed_step contract):
    one ragged attention launch per layer serves the admitting slot's
    chunk AND a decode token for every active slot. Softcap and the
    per-layer sliding windows thread through exactly as in decode/verify.
    Only called with ragged attention enabled (engine gates on it)."""
    del mlp
    c = chunk_tokens.shape[0]
    s = tokens.shape[0]
    xc = _embed_in(params, cfg, chunk_tokens, embeds)   # [C, E]
    xg = _embed_in(params, cfg, tokens)                 # [S, E]
    x = jnp.concatenate([xc, xg])[None]                 # [1, C+S, E]
    positions = cache.lengths
    total = chunk_start + chunk_len
    pos = jnp.concatenate([
        chunk_start + jnp.arange(c, dtype=jnp.int32), positions
    ])[None]

    def attn_fn(q, k, v, win, li):
        oc, og = ragged_paged_attention(
            cache.k, cache.v, cache.page_size,
            q_chunk=q[:, :c], chunk_row=table_row, chunk_start=chunk_start,
            chunk_total=total, k_chunk=k[0, :c], v_chunk=v[0, :c],
            q_group=q[0, c:][:, None], page_table=cache.page_table,
            group_lengths=positions, k_group=k[0, c:][:, None],
            v_group=v[0, c:][:, None], layer=li, use_pallas=cfg.use_pallas,
            logit_softcap=cfg.attn_logit_softcap, window=win, mesh=mesh,
        )
        return jnp.concatenate([oc[0], og[:, 0]]).reshape(1, c + s, -1)

    x, k_ys, v_ys = _scan_layers(params, cfg, x, pos, attn_fn)
    k_new, v_new = k_ys[:, 0], v_ys[:, 0]               # [L, C+S, KVH, D]
    x = _gnorm(x, params["final_norm"], cfg.rms_eps)
    chunk_logits = _unembed(cfg, params, x[0, jnp.maximum(chunk_len - 1, 0)])
    dec_logits = _unembed(cfg, params, x[0, c:])

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
    return chunk_logits, dec_logits, PagedKVCache(
        k=k_pool, v=v_pool,
        page_table=cache.page_table.at[slot].set(table_row),
        lengths=new_lengths, page_size=cache.page_size,
    )


# ---------------------------------------------------------------------------
# HF layout (Gemma2ForCausalLM)
# ---------------------------------------------------------------------------

HF_MAP: dict[str, tuple[str, bool]] = {
    "attn_norm": ("model.layers.{}.input_layernorm.weight", False),
    "wq": ("model.layers.{}.self_attn.q_proj.weight", True),
    "wk": ("model.layers.{}.self_attn.k_proj.weight", True),
    "wv": ("model.layers.{}.self_attn.v_proj.weight", True),
    "wo": ("model.layers.{}.self_attn.o_proj.weight", True),
    "post_attn_norm": ("model.layers.{}.post_attention_layernorm.weight", False),
    "pre_ffn_norm": ("model.layers.{}.pre_feedforward_layernorm.weight", False),
    "w_gate": ("model.layers.{}.mlp.gate_proj.weight", True),
    "w_up": ("model.layers.{}.mlp.up_proj.weight", True),
    "w_down": ("model.layers.{}.mlp.down_proj.weight", True),
    "post_ffn_norm": ("model.layers.{}.post_feedforward_layernorm.weight", False),
}


def hf_map(cfg: ModelConfig) -> dict[str, tuple[str, bool]]:
    return dict(HF_MAP)


def convert_hf_state_dict(
    cfg: ModelConfig, sd: dict[str, Any], dtype=jnp.bfloat16
) -> Params:
    from gridllm_tpu.models import llama

    return llama.convert_state_dict(cfg, sd, HF_MAP, dtype)
