"""Operations and bytes of the SmallThinker decoder (routed ReGLU experts,
no dense feed-forward, untied head), from a configuration file's published
``config.json`` keys: the functions ``costs.py`` documents, found through
``costs.of(config)`` by the configuration's ``"costs"`` key.

A layer outside its experts: q, k, v, o projections, the router and two
norms. An expert: gate, up and down, 3 x hidden x ``moe_ffn_hidden_size``.
At the published sizes a layer is 398,627,840 parameters, 377,487,360 of
them in its 64 experts (benchmark/tests/test_smallthinker_cell.py holds
this file to the hand figures). It stands beside ``costs.py`` and not in a
``family_costs/`` directory, because an accepted test of the harness makes
that directory in a copy of the benchmark and fails where it exists."""

from __future__ import annotations

import costs

DTYPE_BYTES = costs.DTYPE_BYTES


def expert_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["moe_ffn_hidden_size"]


def layer_params(spec: dict) -> int:
    e, d = spec["hidden_size"], spec["head_dim"]
    h, kvh = spec["num_attention_heads"], spec["num_key_value_heads"]
    attention = e * h * d + 2 * e * kvh * d + h * d * e
    router = e * spec["moe_num_primary_experts"]
    return (attention + router + 2 * e
            + spec["moe_num_primary_experts"] * expert_params(spec))


embedding_params = costs.embedding_params      # embedding, head, final norm


def total_params(spec: dict) -> int:
    return spec["num_hidden_layers"] * layer_params(spec) + embedding_params(spec)


def weight_bytes(spec: dict) -> int:
    return total_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def expert_bytes(spec: dict) -> int:
    """EVERY held expert of every layer: what a launch reads of them when
    its rows touch them all (``held_experts`` x ``one_expert_bytes``)."""
    return (spec["num_hidden_layers"] * spec["moe_num_primary_experts"]
            * expert_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")])


def one_expert_bytes(spec: dict) -> int:
    """ONE routed expert of one layer (5.9 M parameters): what a launch
    has to read for each expert its live rows touch."""
    return expert_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def held_experts(spec: dict) -> int:
    """Routed experts a launch passes, summed over the layers: what
    ``gridllm_moe_experts_touched_total`` reads a launch at the most."""
    return spec["num_hidden_layers"] * spec["moe_num_primary_experts"]


def step_weight_bytes(spec: dict, touched: float | None = None) -> float:
    """Weight bytes one decode or verify step has to read: attention, the
    router and the norms of every layer and the output head whole, of the
    embedding only the rows looked up, and of the routed experts the
    `touched` ones (experts with at least one live row, summed over the
    layers: the engine's counter a launch). With no `touched`: every held
    expert, AT MOST what a launch reads, which the all-experts form reads
    whatever the rows."""
    b = DTYPE_BYTES[spec.get("dtype", "bfloat16")]
    head = spec["vocab_size"] * spec["hidden_size"]
    whole = (spec["num_hidden_layers"] * layer_params(spec) + head) * b
    if touched is None:
        return whole
    return whole - (held_experts(spec) - touched) * one_expert_bytes(spec)


def kv_bytes_per_token(spec: dict, kv_dtype_bytes: int = 2) -> int:
    """Keys and values of one position over every layer (a window layer
    keeps its positions in the shared page table too)."""
    return (spec["num_hidden_layers"] * 2 * spec["num_key_value_heads"]
            * spec["head_dim"] * kv_dtype_bytes)


def kv_launch_bytes(spec: dict, per_launch) -> float | None:
    """Cache bytes one verify / decode launch READS: a window layer reads
    min(context, 4,096) rows of a slot, a global layer the context. The
    program counts exactly that (``costs.WINDOW_TOKENS``: Σ over live slots
    of the mean over layers of min(context, window)), so the bytes are one
    position's over every layer times it. Under the window the counter is
    the context counter and this is context x ``kv_bytes_per_token``."""
    tokens = per_launch(costs.WINDOW_TOKENS)
    return None if tokens is None else kv_bytes_per_token(spec) * tokens


def flash_prefill_flops(spec: dict, t: int) -> float:
    """One causal flash-attention call over a bucket of t positions, one
    layer: QK^T and PV are 2*t*t*D each per query head, half of the
    square. Buckets reach 2048 at most here, under the window of 4096,
    so a window layer does the same work as a global one."""
    return 0.5 * 2 * 2.0 * spec["num_attention_heads"] * t * t * spec["head_dim"]


def chip_share(spec: dict) -> dict | None:
    """One chip holds the whole configuration; a mesh has no rule here
    (experts over ``ep`` are not served by this family yet): None."""
    if any(size > 1 for size in costs.mesh_axes(spec).values()):
        return None
    return {"weights": 1, "kv": 1, "heads": 1}
