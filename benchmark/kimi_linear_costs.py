"""Operations and bytes of the Kimi-Linear decoder (Kimi Delta Attention
3:1 with latent attention, a dense first layer, routed experts behind a
sigmoid router with a selection bias and a shared expert, untied head),
from a configuration file's published ``config.json`` keys: the functions
``costs.py`` documents, found through ``costs.of(config)`` by the
configuration's ``"costs"`` key, and those of the recurrent state, its two
kernels and the experts HELD (``num_experts`` is what this chip holds of
the router's ``router_experts``).

At Kimi-Linear-48B-A3B's sizes (benchmark/tests/test_kimilinear_cell.py
holds this file to the hand figures of ISSUE 51): a KDA mixer 39,514,272
(W_q, W_k, W_v, W_o 37,748,736; the convolutions 49,152; the decay's and
the gate's low-rank pairs 819,200 each; W_beta 73,728; A_log, dt_bias, the
gated norm 4,256), an MLA mixer 29,114,880 (W_q 14,155,776; W_kva
1,327,104; the latent's norm 512; W_kvb 4,194,304; W_o 9,437,184), two
block norms 4,608; layer 1 (KDA, a dense SwiGLU 63,700,992) 103,219,872;
one expert 7,077,888; an expert layer's feed-forward with 64 held
460,652,800 (the router 589,824 and its bias 256, 64 experts, the shared
one); embedding, head and final norm 754,977,024; layers 1-8 with 64 held
4,338,599,872; all 27 with every expert 49,122,681,728."""

from __future__ import annotations

import costs

DTYPE_BYTES = costs.DTYPE_BYTES
STATE_BYTES = 4          # the state is float32


def _kda(spec: dict) -> tuple[int, int, int, int]:
    """(heads, key width, value width, convolution taps)."""
    la = spec["linear_attn_config"]
    return (la["num_heads"], la["head_dim"], la["head_dim"],
            la["short_conv_kernel_size"])


def conv_channels(spec: dict) -> int:
    h, dk, dv, _ = _kda(spec)
    return h * (2 * dk + dv)


def kda_mixer_params(spec: dict) -> int:
    e = spec["hidden_size"]
    h, dk, dv, taps = _kda(spec)
    low = dk                       # the low-rank width: assumed, = head_dim
    return (2 * e * h * dk + e * h * dv + h * dv * e + taps * conv_channels(spec)
            + e * low + low * h * dk + e * low + low * h * dv
            + e * h + h + h * dk + dv)


def mla_mixer_params(spec: dict) -> int:
    e, h, r = spec["hidden_size"], spec["num_attention_heads"], spec["kv_lora_rank"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    return e * h * (dn + dr) + e * (r + dr) + r + r * h * (dn + dv) + h * dv * e


def expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["moe_intermediate_size"]


def router_experts(spec: dict) -> int:
    return spec.get("router_experts", spec["num_experts"])


def expert_ffn_params(spec: dict) -> int:
    """One expert layer's feed-forward as held HERE: the router over all
    the experts and its bias, the held experts, the shared ones."""
    x = router_experts(spec)
    return (spec["hidden_size"] * x + x
            + (spec["num_experts"] + spec.get("num_shared_experts", 0))
            * expert_params(spec))


def layer_kinds(spec: dict) -> list[bool]:
    """For each layer held, whether its mixer is KDA."""
    kda = set(spec["linear_attn_config"]["kda_layers"])
    return [i + 1 in kda for i in range(spec["num_hidden_layers"])]


def layer_counts(spec: dict) -> tuple[int, int]:
    """(dense layers, expert layers) of the layers held."""
    dense = min(spec.get("first_k_dense_replace", 0), spec["num_hidden_layers"])
    return dense, spec["num_hidden_layers"] - dense


def mixer_counts(spec: dict) -> tuple[int, int]:
    """(KDA layers, MLA layers) of the layers held."""
    n = sum(layer_kinds(spec))
    return n, spec["num_hidden_layers"] - n


def layer_params(spec: dict, i: int) -> int:
    e = spec["hidden_size"]
    mixer = (kda_mixer_params(spec) if layer_kinds(spec)[i]
             else mla_mixer_params(spec))
    ffn = (3 * e * spec["intermediate_size"] if i < layer_counts(spec)[0]
           else expert_ffn_params(spec))
    return mixer + 2 * e + ffn


embedding_params = costs.embedding_params      # embedding, head, final norm


def total_params(spec: dict) -> int:
    return sum(layer_params(spec, i)
               for i in range(spec["num_hidden_layers"])) + embedding_params(spec)


def weight_bytes(spec: dict) -> int:
    return total_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def expert_bytes(spec: dict) -> int:
    """One routed expert's bytes."""
    return expert_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def held_experts(spec: dict) -> int:
    """Held routed experts a launch passes, summed over the expert layers:
    what ``gridllm_moe_experts_touched_total`` reads a launch at the most."""
    return spec["num_experts"] * layer_counts(spec)[1]


def held_expert_bytes(spec: dict, touched: float | None = None) -> float:
    """Bytes of routed experts one launch reads, every expert layer:
    `touched` experts (summed over layers: the engine's counter a launch),
    else every held one."""
    if touched is None:
        touched = held_experts(spec)
    return float(touched) * expert_bytes(spec)


def step_weight_bytes(spec: dict, touched: float | None = None) -> float:
    """Weight bytes one decode or verify step has to read: the mixers, the
    norms, the dense layer, every router and shared expert and the output
    head whole, of the embedding only the rows looked up, and of the held
    experts the `touched` ones (``held_expert_bytes``). With no `touched`:
    every held expert, AT MOST what a launch reads, which the all-experts
    form reads whatever the rows."""
    b = DTYPE_BYTES[spec.get("dtype", "bfloat16")]
    head = spec["vocab_size"] * spec["hidden_size"]
    whole = (total_params(spec) - embedding_params(spec) + head) * b
    if touched is None:
        return whole
    return whole - (held_experts(spec) - touched) * expert_bytes(spec)


def kv_bytes_per_token(spec: dict, kv_dtype_bytes: int = 2) -> int:
    """The latent row of one position over the layers that OWN pages: the
    MLA layers alone (two of eight: 2 x 576 values = 2,304 B), as the model
    holds it; the pool stores a row at 640 lanes (1,280 B), which a reader
    that multiplies by this leaves out: it reads low here, never high."""
    return (mixer_counts(spec)[1]
            * (spec["kv_lora_rank"] + spec["qk_rope_head_dim"]) * kv_dtype_bytes)


def state_bytes_per_slot(spec: dict, conv_dtype_bytes: int = 2) -> int:
    """What one slot (or one snapshot) holds of its past in the KDA
    layers: the float32 state [dk, dv] a head and the convolution's last
    K - 1 rows, every KDA layer (12,582,912 + 442,368 B at six)."""
    h, dk, dv, taps = _kda(spec)
    tail = (taps - 1) * conv_channels(spec)
    return mixer_counts(spec)[0] * (
        h * dk * dv * STATE_BYTES + tail * conv_dtype_bytes)


def flash_prefill_flops(spec: dict, t: int) -> float:
    """One causal attention over a bucket of t positions, one MLA layer at
    its expanded widths. (This family admits through the mixed step; no
    flash-prefill call is expected in its cells.)"""
    d = spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"] + spec["v_head_dim"]
    return 0.5 * 2.0 * spec["num_attention_heads"] * t * t * d


def kda_chunk_flops(spec: dict, rows: int) -> float:
    """Operations the delta rule's EQUATIONS need for `rows` tokens of one
    slot, every KDA layer: per token and head the decay (dk dv), S^T k
    (2 dk dv), the rank-one update (2 dk dv) and S^T q (2 dk dv). The
    chunked form spends more (the blocks' pair terms and triangular
    systems): that reads as distance from the roofline."""
    h, dk, dv, _ = _kda(spec)
    return float(rows) * mixer_counts(spec)[0] * h * 7.0 * dk * dv


def kda_step_bytes(spec: dict, live_slots: float, rows: int) -> float:
    """Bytes one step launch must move for the delta rule, every KDA
    layer: each LIVE slot's state read once and written once, and its
    rows' q, k, v and the decay a key channel (float32)."""
    h, dk, dv, _ = _kda(spec)
    per_slot = 2 * h * dk * dv * STATE_BYTES + rows * h * (3 * dk + dv) * 4
    return float(live_slots) * mixer_counts(spec)[0] * per_slot


def chip_share(spec: dict) -> dict | None:
    """One chip holds the whole configuration (its share of the experts is
    what the file's keys count); the family refuses a mesh."""
    if any(size > 1 for size in costs.mesh_axes(spec).values()):
        return None
    return {"weights": 1, "kv": 1, "heads": 1}
