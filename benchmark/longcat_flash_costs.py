"""Operations and bytes of the LongCat-Flash decoder (a block of two
latent-attention sublayers with a low-rank query, two dense SwiGLUs and a
shortcut-connected expert layer with zero-compute experts, untied head),
from a configuration file's published ``config.json`` keys (``num_layers``,
``ffn_hidden_size``, ``expert_ffn_hidden_size``, ``moe_topk``, ...): the
functions ``costs.py`` documents, found through ``costs.of(config)`` by the
configuration's ``"costs"`` key, and those of the latent read in its
absorbed form, flops AND bytes, so that a share can be taken of whichever
bound binds. ``n_routed_experts`` is what this chip holds of the router's
``router_experts``; the router is ``router_experts + zero_expert_num`` wide;
``vocab_held`` the rows of the vocabulary held of ``vocab_size``.

At LongCat-Flash-Omni's sizes (benchmark/tests/test_longcat_cell.py holds
this file to the hand figures of ISSUE 57): one MLA sublayer 90,572,800
(W_qa 9,437,184; its norm 1,536; W_qb 18,874,368; W_kva 3,538,944; the
latent's norm 512; W_kvb 8,388,608; W_o 50,331,648); one dense SwiGLU
226,492,416; four block norms 24,576; the router 4,718,592 and its
selection bias 768: a block outside its routed experts 638,874,368; one
expert 37,748,736; embedding, head and final norm at a vocabulary of
16,384 201,332,736; four blocks with 16 experts held 5,172,749,312
(10.35 GB in bfloat16); a token's latent rows over the 8 sublayers 9,216 B
as the equations have them (10,240 B as the pool stores them, 640 lanes)."""

from __future__ import annotations

import costs

DTYPE_BYTES = costs.DTYPE_BYTES
# the pool stores a row lane-padded: 576 values in 640 (deepseek_v2_costs)
STORED_ROW_VALUES = 640
ATTN_SUBLAYERS = 2          # attention sublayers (and dense SwiGLUs) a block
# the engine's count of the positions a verify / decode launch attends
CTX_TOKENS = "gridllm_engine_verify_ctx_tokens_total"


def _b(spec: dict) -> int:
    return DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def mla_params(spec: dict) -> int:
    """One latent-attention sublayer: the low-rank query's pair and norm,
    the latent's down-projection and norm, its up-projection, W_o."""
    e, h = spec["hidden_size"], spec["num_attention_heads"]
    r, rq = spec["kv_lora_rank"], spec["q_lora_rank"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    return (e * rq + rq + rq * h * (dn + dr) + e * (r + dr) + r
            + r * h * (dn + dv) + h * dv * e)


def dense_ffn_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["ffn_hidden_size"]


def expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["expert_ffn_hidden_size"]


def router_width(spec: dict) -> int:
    """The router's outputs: every routed expert of the model and the
    zero-compute ones."""
    return (spec.get("router_experts", spec["n_routed_experts"])
            + (spec.get("zero_expert_num") or 0))


def block_params_outside_experts(spec: dict) -> int:
    """Both attentions, both dense SwiGLUs, the four block norms, the
    router at its full width and its selection bias: what every chip of
    the group that shares a block holds alike."""
    e = spec["hidden_size"]
    return (ATTN_SUBLAYERS * (mla_params(spec) + dense_ffn_params(spec) + 2 * e)
            + e * router_width(spec) + router_width(spec))


def block_params(spec: dict) -> int:
    return (block_params_outside_experts(spec)
            + spec["n_routed_experts"] * expert_params(spec))


def embedding_params(spec: dict) -> int:
    """Token embedding, the untied output head and the final norm, over
    the rows of the vocabulary HELD HERE (``vocab_held``: this chip's
    slice; ``vocab_size`` is the published count)."""
    rows = spec.get("vocab_held") or spec["vocab_size"]
    return 2 * rows * spec["hidden_size"] + spec["hidden_size"]


def total_params(spec: dict) -> int:
    return spec["num_layers"] * block_params(spec) + embedding_params(spec)


def weight_bytes(spec: dict) -> int:
    return total_params(spec) * _b(spec)


def one_expert_bytes(spec: dict) -> int:
    """ONE routed expert of one block (37.7 M parameters, 75.5 MB): what a
    launch has to read for each expert its live rows touch. A zero-compute
    pick reads nothing."""
    return expert_params(spec) * _b(spec)


def held_experts(spec: dict) -> int:
    """Held routed experts a launch passes, summed over the blocks: what
    ``gridllm_moe_experts_touched_total`` reads a launch at the most."""
    return spec["n_routed_experts"] * spec["num_layers"]


def held_expert_bytes(spec: dict, touched: float | None = None) -> float:
    """Bytes of routed experts one launch reads, every block: `touched`
    experts (summed over blocks: the engine's counter a launch), else
    every held one."""
    if touched is None:
        touched = held_experts(spec)
    return float(touched) * one_expert_bytes(spec)


def step_weight_bytes(spec: dict, touched: float | None = None) -> float:
    """Weight bytes one decode or verify step has to read: both
    attentions, both dense SwiGLUs, the norms, every router and the output
    head whole, of the embedding only the rows looked up, and of the held
    experts the `touched` ones. With no `touched`: every held expert, AT
    MOST what a launch reads."""
    head = (spec.get("vocab_held") or spec["vocab_size"]) * spec["hidden_size"]
    whole = (total_params(spec) - embedding_params(spec) + head) * _b(spec)
    if touched is None:
        return whole
    return whole - (held_experts(spec) - touched) * one_expert_bytes(spec)


def pool_layers(spec: dict) -> int:
    """Layers of the latent pool: a block owns one a sublayer."""
    return ATTN_SUBLAYERS * spec["num_layers"]


def kv_row_values(spec: dict) -> int:
    """Values of one token's cache row in one sublayer, as the equations
    have it: the latent and its RoPE key."""
    return spec["kv_lora_rank"] + spec["qk_rope_head_dim"]


def kv_bytes_per_token(spec: dict, kv_dtype_bytes: int = 2) -> int:
    """One position's latent rows over every sublayer, as the equations
    have them (9,216 B at four blocks). The pool stores 640 values a row
    (`STORED_ROW_VALUES`): 10,240 B; the rooflines count the row once and
    unpadded, so padding reads as distance from the roofline."""
    return pool_layers(spec) * kv_row_values(spec) * kv_dtype_bytes


def kv_launch_bytes(spec: dict, per_launch) -> float | None:
    """Cache bytes ONE verify / decode launch reads: every position of
    every live context, in every sublayer (no window, ring or selection),
    from the engine's context-token counter a launch of the capture."""
    tokens = per_launch(CTX_TOKENS)
    return None if tokens is None else tokens * kv_bytes_per_token(spec)


def flash_prefill_flops(spec: dict, t: int) -> float:
    """One causal attention over a bucket of t positions in the expanded
    form, one sublayer. (This family admits through the mixed step; no
    flash-prefill call is expected in its cells.)"""
    return 0.5 * 2.0 * spec["num_attention_heads"] * t * t * (
        spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"] + spec["v_head_dim"])


def latent_attn_flops(spec: dict, rows: int, ctx: int,
                      form: str = "absorbed") -> float:
    """Operations of `rows` query tokens over `ctx` cached positions, one
    sublayer, every head. Absorbed: scores over the row (rank + rope) and
    values over the latent (rank), 2 x rows x ctx x (576 + 512) a head:
    139,264 a token a key at 64 heads (71.3 MFLOP a key for a 512-row
    chunk). Expanded: 2 x rows x ctx x (192 + 128) a head plus the
    up-projection of the ctx latents (37.8 MFLOP a key for the chunk)."""
    h, r = spec["num_attention_heads"], spec["kv_lora_rank"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    if form == "absorbed":
        return 2.0 * rows * ctx * h * ((r + dr) + r)
    return 2.0 * rows * ctx * h * (dn + dr + dv) + 2.0 * ctx * r * h * (dn + dv)


def absorb_flops(spec: dict, rows: int) -> float:
    """The two small products around an absorbed read, one sublayer: q' =
    q_nope Wk^T and a = o Wv, 2 x rows x heads x rank x (nope + v)."""
    return 2.0 * rows * spec["num_attention_heads"] * spec["kv_lora_rank"] * (
        spec["qk_nope_head_dim"] + spec["v_head_dim"])


def latent_attn_bytes(spec: dict, ctx: int, kv_dtype_bytes: int = 2) -> float:
    """Bytes of `ctx` cached rows of one sublayer, read once."""
    return float(ctx * kv_row_values(spec) * kv_dtype_bytes)


def chip_share(spec: dict) -> dict | None:
    """One chip holds the whole configuration (its share of the experts
    and of the vocabulary is what the file's keys count); the family
    refuses a mesh."""
    if any(size > 1 for size in costs.mesh_axes(spec).values()):
        return None
    return {"weights": 1, "kv": 1, "heads": 1}
