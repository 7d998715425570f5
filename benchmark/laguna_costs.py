"""Operations and bytes of the Laguna decoder (attention layers of two
shapes, a gate a head, a leading dense layer, routed experts behind a
sigmoid router with a shared expert, untied head), from a configuration
file's published ``config.json`` keys: the functions ``costs.py``
documents, found through ``costs.of(config)`` by the configuration's
``"costs"`` key, with the heads by layer and a window layer's cache.

At Laguna-XS.2's sizes (benchmark/tests/test_laguna_cell.py holds this
file to the hand figures of ISSUE 45): global attention (48 heads)
29,458,432 (q 12,582,912; k and v 2,097,152 each; o 12,582,912; the gate
98,304), window attention (64 heads) 37,879,808; two norms 4,096; layer 0
(global, a dense SwiGLU of 8,192: 50,331,648) 79,794,176; an expert layer
beside its attention 808,976,384 (router 524,288; 256 experts of
3,145,728; shared 3,145,728); embedding, head and final norm 411,043,840;
layers 0-4 3,869,857,792; all 40 33,442,596,864. It stands beside
``costs.py`` for the reason ``smallthinker_costs.py`` gives."""

from __future__ import annotations

import costs

DTYPE_BYTES = costs.DTYPE_BYTES


def kinds(spec: dict) -> list[bool]:
    """For each layer held, whether it is a window layer."""
    return [k == "sliding_attention"
            for k in spec["layer_types"][:spec["num_hidden_layers"]]]


def layer_heads(spec: dict) -> list[int]:
    n = spec["num_hidden_layers"]
    return list(spec.get("num_attention_heads_per_layer")
                or [spec["num_attention_heads"]] * n)[:n]


def attention_params(spec: dict, heads: int) -> int:
    e, d, kvh = spec["hidden_size"], spec["head_dim"], spec["num_key_value_heads"]
    gate = e * heads if spec.get("gating") else 0
    return 2 * e * heads * d + 2 * e * kvh * d + gate


def expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["moe_intermediate_size"]


def shared_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * (
        spec.get("shared_expert_intermediate_size") or 0)


def expert_layer_params(spec: dict) -> int:
    """The router, every routed expert and the shared one of ONE layer."""
    return (spec["hidden_size"] * spec["num_experts"]
            + spec["num_experts"] * expert_params(spec) + shared_params(spec))


def sparse(spec: dict) -> list[bool]:
    return [m == "sparse"
            for m in spec["mlp_layer_types"][:spec["num_hidden_layers"]]]


def layer_counts(spec: dict) -> tuple[int, int]:
    """(dense layers, expert layers) of the layers held."""
    n = sum(sparse(spec))
    return spec["num_hidden_layers"] - n, n


def layer_params(spec: dict, i: int) -> int:
    e = spec["hidden_size"]
    mlp = (expert_layer_params(spec) if sparse(spec)[i]
           else 3 * e * spec["intermediate_size"])
    return attention_params(spec, layer_heads(spec)[i]) + 2 * e + mlp


embedding_params = costs.embedding_params      # embedding, head, final norm


def total_params(spec: dict) -> int:
    return sum(layer_params(spec, i)
               for i in range(spec["num_hidden_layers"])) + embedding_params(spec)


def active_params(spec: dict) -> int:
    """Parameters one token's forward reads: top-k of the experts."""
    idle = (spec["num_experts"] - spec["num_experts_per_tok"]) * expert_params(spec)
    return total_params(spec) - layer_counts(spec)[1] * idle


def weight_bytes(spec: dict) -> int:
    return total_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def expert_bytes(spec: dict) -> int:
    """One routed expert's bytes."""
    return expert_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def expert_flops(spec: dict, rows: float) -> float:
    """Operations the model NEEDS for `rows` token rows in one expert
    layer's routed products: top-k experts a row, whatever form computes
    them."""
    return 2.0 * rows * spec["num_experts_per_tok"] * expert_params(spec)


def held_experts(spec: dict) -> int:
    """Routed experts a launch passes, summed over the expert layers: what
    ``gridllm_moe_experts_touched_total`` reads a launch at the most."""
    return layer_counts(spec)[1] * spec["num_experts"]


def step_weight_bytes(spec: dict, touched: float | None = None) -> float:
    """Weight bytes one decode or verify step has to read: attention, the
    norms, the dense layer, every router and shared expert and the output
    head whole, of the embedding only the rows looked up, and of the
    routed experts the `touched` ones (experts with at least one live row,
    summed over the layers: the engine's counter a launch) at
    ``expert_bytes`` each. With no `touched`: every held expert, AT MOST
    what a launch reads, which the all-experts form reads whatever the
    rows."""
    b = DTYPE_BYTES[spec.get("dtype", "bfloat16")]
    head = spec["vocab_size"] * spec["hidden_size"]
    whole = (total_params(spec) - embedding_params(spec) + head) * b
    if touched is None:
        return whole
    return whole - (held_experts(spec) - touched) * expert_bytes(spec)


def row_bytes(spec: dict, kv_dtype_bytes: int = 2) -> int:
    """K and V of one position in one layer."""
    return 2 * spec["num_key_value_heads"] * spec["head_dim"] * kv_dtype_bytes


def kv_bytes_per_token(spec: dict, kv_dtype_bytes: int = 2,
                       context: int | None = None) -> float:
    """Keys and values of one position over the layers that keep it. With
    no `context`: the global layers alone (8,192 B at two of five layers),
    which is what a position costs in pages; a window layer keeps and
    reads a window of rows a slot, not the context (what a launch READS is
    ``kv_launch_bytes``). With a `context` length: the global layers'
    rows, and of a window layer's min(context, window) rows a position's
    share."""
    win = sum(kinds(spec))
    glob = spec["num_hidden_layers"] - win
    if context is None:
        return glob * row_bytes(spec, kv_dtype_bytes)
    seen = min(context, spec["sliding_window"]) / max(context, 1)
    return (glob + win * seen) * row_bytes(spec, kv_dtype_bytes)


def kv_launch_bytes(spec: dict, per_launch) -> float | None:
    """Cache bytes one verify / decode launch READS: a global layer the
    context from its pages, a window layer min(context, 512) rows from its
    ring (3 x 512 x 4,096 B = 6.3 MB a live slot past the window). The
    program counts that (``costs.WINDOW_TOKENS``: Σ over live slots of the
    mean over the layers held of min(context, window)), so the bytes are
    one layer's row times the layers times it."""
    tokens = per_launch(costs.WINDOW_TOKENS)
    return None if tokens is None else table_bytes_per_token(spec) * tokens


def table_bytes_per_token(spec: dict, kv_dtype_bytes: int = 2) -> int:
    """What a position would cost if ONE page table served every layer
    (20,480 B at five layers): what the rings are measured against."""
    return spec["num_hidden_layers"] * row_bytes(spec, kv_dtype_bytes)


def flash_prefill_flops(spec: dict, t: int) -> float:
    """One causal attention over a bucket of t positions, one layer, at
    the widest layer's heads: half of the square. (This family admits
    through the mixed step; no flash-prefill call is expected in its
    cells.)"""
    return 0.5 * 2 * 2.0 * max(layer_heads(spec)) * t * t * spec["head_dim"]


def chip_share(spec: dict) -> dict | None:
    """One chip holds the whole configuration; the family refuses a mesh."""
    if any(size > 1 for size in costs.mesh_axes(spec).values()):
        return None
    return {"weights": 1, "kv": 1, "heads": 1}
