"""Operations and bytes of the DeepSeek-V2 decoder (latent attention, a
leading dense layer, routed experts with shared ones, untied head), from a
configuration file's published ``config.json`` keys: the functions
``costs.py`` documents, found through ``costs.of(config)`` by the
configuration's ``"costs"`` key, and two for the latent read path.

At DeepSeek-V2-Lite's sizes (benchmark/tests/test_dsv2lite_cell.py holds
this file to the hand figures of ISSUE 36): attention of any layer
13,763,072 (q 6,291,456; kv_a 1,179,648; its norm 512; kv_b 2,097,152; o
4,194,304), two norms 4,096; the dense layer 81,007,104; a routed layer
584,847,872 (router 131,072; 64 experts of 8,650,752; shared 17,301,504);
embedding, head and final norm 419,432,448; ten layers 5,764,070,400.
It stands beside ``costs.py`` for the reason ``smallthinker_costs.py``
gives."""

from __future__ import annotations

import costs

DTYPE_BYTES = costs.DTYPE_BYTES
# the pool stores a row lane-padded: 576 values in 640 (Mosaic tiles the
# page [128, 576] at 640 lanes in HBM and cannot slice 576 of them;
# PERF.md, PR 36). The roofline counts the row as the equations have it.
STORED_ROW_VALUES = 640


def attention_params(spec: dict) -> int:
    e, h, r = spec["hidden_size"], spec["num_attention_heads"], spec["kv_lora_rank"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    return (e * h * (dn + dr) + e * (r + dr) + r + r * h * (dn + dv)
            + h * dv * e)


def expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["moe_intermediate_size"]


def dense_layer_params(spec: dict) -> int:
    e = spec["hidden_size"]
    return attention_params(spec) + 2 * e + 3 * e * spec["intermediate_size"]


def routed_layer_params(spec: dict) -> int:
    e = spec["hidden_size"]
    return (attention_params(spec) + 2 * e + e * spec["n_routed_experts"]
            + (spec["n_routed_experts"] + (spec.get("n_shared_experts") or 0))
            * expert_params(spec))


def layer_counts(spec: dict) -> tuple[int, int]:
    """(dense layers, routed layers) of the layers held."""
    dense = min(spec.get("first_k_dense_replace", 0), spec["num_hidden_layers"])
    return dense, spec["num_hidden_layers"] - dense


embedding_params = costs.embedding_params      # embedding, head, final norm


def total_params(spec: dict) -> int:
    dense, routed = layer_counts(spec)
    return (dense * dense_layer_params(spec)
            + routed * routed_layer_params(spec) + embedding_params(spec))


def weight_bytes(spec: dict) -> int:
    return total_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def expert_bytes(spec: dict) -> int:
    """EVERY held routed expert of every routed layer (``held_experts`` x
    ``one_expert_bytes``)."""
    return (layer_counts(spec)[1] * spec["n_routed_experts"]
            * expert_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")])


def expert_layer_bytes(spec: dict) -> int:
    """The router, every held routed expert and the shared experts of
    every routed layer: what the expert layers of one launch read at most."""
    per_layer = (spec["hidden_size"] * spec["n_routed_experts"]
                 + (spec["n_routed_experts"] + (spec.get("n_shared_experts") or 0))
                 * expert_params(spec))
    return (layer_counts(spec)[1] * per_layer
            * DTYPE_BYTES[spec.get("dtype", "bfloat16")])


def one_expert_bytes(spec: dict) -> int:
    """ONE routed expert of one layer (8.65 M parameters): what a launch
    has to read for each expert its live rows touch."""
    return expert_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def held_experts(spec: dict) -> int:
    """Routed experts a launch passes, summed over the routed layers: what
    ``gridllm_moe_experts_touched_total`` reads a launch at the most."""
    return layer_counts(spec)[1] * spec["n_routed_experts"]


def step_weight_bytes(spec: dict, touched: float | None = None) -> float:
    """Weight bytes one decode or verify step has to read: attention, the
    norms, the dense layer, every router and shared expert and the output
    head whole, of the embedding only the rows looked up, and of the
    routed experts the `touched` ones (experts with at least one live row,
    summed over the layers: the engine's counter a launch). With no
    `touched`: every held expert, AT MOST what a launch reads, which the
    all-experts form reads whatever the rows."""
    b = DTYPE_BYTES[spec.get("dtype", "bfloat16")]
    head = spec["vocab_size"] * spec["hidden_size"]
    whole = (total_params(spec) - embedding_params(spec) + head) * b
    if touched is None:
        return whole
    return whole - (held_experts(spec) - touched) * one_expert_bytes(spec)


def kv_row_values(spec: dict) -> int:
    """Values of one token's cache row in one layer, as the equations have
    it: the latent and its RoPE key."""
    return spec["kv_lora_rank"] + spec["qk_rope_head_dim"]


def kv_bytes_per_token(spec: dict, kv_dtype_bytes: int = 2) -> int:
    """One position's latent rows over every layer, as the equations have
    them (11,520 B at ten layers). The pool stores 640 values a row
    (`STORED_ROW_VALUES`): 12,800 B; the rooflines count the row once and
    unpadded, so padding reads as distance from the roofline."""
    return spec["num_hidden_layers"] * kv_row_values(spec) * kv_dtype_bytes


def per_head_row_values(spec: dict) -> int:
    """What K and V per head would store of one token in one layer."""
    return spec["num_attention_heads"] * (
        spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"] + spec["v_head_dim"])


def flash_prefill_flops(spec: dict, t: int) -> float:
    """One causal attention over a bucket of t positions in the expanded
    form, one layer: QK^T at nope + rope and PV at v a head, half of the
    square. (This family admits through the mixed step; no flash-prefill
    call is expected in its cells.)"""
    return 0.5 * 2.0 * spec["num_attention_heads"] * t * t * (
        spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"] + spec["v_head_dim"])


def latent_attn_flops(spec: dict, rows: int, ctx: int,
                      form: str = "absorbed") -> float:
    """Operations of `rows` query tokens over `ctx` cached positions, one
    layer, every head. Absorbed: scores over the row (rank + rope) and
    values over the latent (rank), 2 x rows x ctx x (576 + 512) a head.
    Expanded: 2 x rows x ctx x (192 + 128) a head plus the up-projection of
    the ctx latents, 2 x ctx x rank x heads x (nope + v)."""
    h, r = spec["num_attention_heads"], spec["kv_lora_rank"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    if form == "absorbed":
        return 2.0 * rows * ctx * h * ((r + dr) + r)
    return 2.0 * rows * ctx * h * (dn + dr + dv) + 2.0 * ctx * r * h * (dn + dv)


def absorb_flops(spec: dict, rows: int) -> float:
    """The two small products around an absorbed read, one layer: q' =
    q_nope Wk^T and a = o Wv, 2 x rows x heads x rank x (nope + v)."""
    return 2.0 * rows * spec["num_attention_heads"] * spec["kv_lora_rank"] * (
        spec["qk_nope_head_dim"] + spec["v_head_dim"])


def latent_attn_bytes(spec: dict, ctx: int, kv_dtype_bytes: int = 2) -> float:
    """Bytes of `ctx` cached rows of one layer, read once."""
    return float(ctx * kv_row_values(spec) * kv_dtype_bytes)


def chip_share(spec: dict) -> dict | None:
    """One chip holds the whole configuration; the family refuses a mesh."""
    if any(size > 1 for size in costs.mesh_axes(spec).values()):
        return None
    return {"weights": 1, "kv": 1, "heads": 1}
