"""Operations and bytes of the Olmo-Hybrid decoder (gated delta-rule
layers 3:1 with full attention, untied head), from a configuration file's
published ``config.json`` keys: the functions ``costs.py`` documents, found
through ``costs.of(config)`` by the configuration's ``"costs"`` key, and
three for the recurrent state and its two kernels.

At Olmo-Hybrid-7B's sizes (benchmark/tests/test_olmohybrid_cell.py holds
this file to the hand figures of ISSUE 42): a linear layer's mixer
88,473,600 (W_q, W_k 3840 x 2880 each; W_v, W_g 3840 x 5760 each; W_o 5760
x 3840) + 230,400 (W_a, W_b) + 46,080 (three convolutions of 4 taps over
11,520 channels) + 252 (A_log, dt_bias, the gated norm), two block norms
7,680, SwiGLU 126,812,160: 215,570,172. A full layer 58,982,400 + 15,360
(QK-norms, block norms) + SwiGLU: 185,809,920. A period of four
832,520,436; embedding, head and final norm 770,707,200; five periods
4,933,309,380. It stands beside ``costs.py`` for the reason
``smallthinker_costs.py`` gives."""

from __future__ import annotations

import costs

DTYPE_BYTES = costs.DTYPE_BYTES
STATE_BYTES = 4          # the state is float32, as the published kernels keep it


def _linear(spec: dict) -> tuple[int, int, int]:
    return (spec["linear_num_key_heads"], spec["linear_key_head_dim"],
            spec["linear_value_head_dim"])


def conv_channels(spec: dict) -> int:
    h, dk, dv = _linear(spec)
    return h * (2 * dk + dv)


def swiglu_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["intermediate_size"]


def linear_layer_params(spec: dict) -> int:
    e = spec["hidden_size"]
    h, dk, dv = _linear(spec)
    mixer = (2 * e * h * dk + 2 * e * h * dv + h * dv * e + 2 * e * h
             + spec["linear_conv_kernel_dim"] * conv_channels(spec)
             + 2 * h + dv)
    return mixer + 2 * e + swiglu_params(spec)


def full_layer_params(spec: dict) -> int:
    e = spec["hidden_size"]
    return 4 * e * e + 4 * e + swiglu_params(spec)


def layer_counts(spec: dict) -> tuple[int, int]:
    """(linear layers, full layers) of the layers held."""
    kinds = spec["layer_types"][:spec["num_hidden_layers"]]
    linear = sum(k == "linear_attention" for k in kinds)
    return linear, len(kinds) - linear


embedding_params = costs.embedding_params      # embedding, head, final norm


def total_params(spec: dict) -> int:
    linear, full = layer_counts(spec)
    return (linear * linear_layer_params(spec) + full * full_layer_params(spec)
            + embedding_params(spec))


def weight_bytes(spec: dict) -> int:
    return total_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def step_weight_bytes(spec: dict) -> int:
    """Weight bytes one decode or verify step must read: every layer and
    the output head; of the embedding only the rows looked up."""
    b = DTYPE_BYTES[spec.get("dtype", "bfloat16")]
    head = spec["vocab_size"] * spec["hidden_size"]
    return (total_params(spec) - embedding_params(spec) + head) * b


def kv_bytes_per_token(spec: dict, kv_dtype_bytes: int = 2) -> int:
    """Keys and values of one position over the layers that OWN pages: the
    full layers alone (five of twenty: 76,800 B)."""
    return (layer_counts(spec)[1] * 2 * spec["num_key_value_heads"]
            * costs.head_dim(spec) * kv_dtype_bytes)


def state_bytes_per_slot(spec: dict, conv_dtype_bytes: int = 2) -> int:
    """What one slot (or one snapshot) holds of its past in the linear
    layers: the float32 state [dk, dv] a head and the convolution's last
    K - 1 rows, every linear layer (33,177,600 + 1,036,800 B at 15)."""
    h, dk, dv = _linear(spec)
    tail = (spec["linear_conv_kernel_dim"] - 1) * conv_channels(spec)
    return layer_counts(spec)[0] * (
        h * dk * dv * STATE_BYTES + tail * conv_dtype_bytes)


def flash_prefill_flops(spec: dict, t: int) -> float:
    """One causal attention over a bucket of t positions, one full layer.
    (This family admits through the mixed step; no flash-prefill call is
    expected in its cells.)"""
    return costs.flash_prefill_flops(spec, t)


def gdn_chunk_flops(spec: dict, rows: int) -> float:
    """Operations the delta rule's EQUATIONS need for `rows` tokens of one
    slot, every linear layer: per token and head the decay (dk dv), S^T k
    (2 dk dv), the rank-one update (2 dk dv) and S^T q (2 dk dv). The
    chunked form spends more (the blocks' triangular systems, products at
    a packed width): that reads as distance from the roofline."""
    h, dk, dv = _linear(spec)
    return float(rows) * layer_counts(spec)[0] * h * 7.0 * dk * dv


def gdn_step_bytes(spec: dict, live_slots: float, rows: int) -> float:
    """Bytes one step launch must move for the delta rule, every linear
    layer: each LIVE slot's state read once and written once, and its
    rows' q, k and v (float32). What any implementation must move; a
    kernel that also moves the states of slots that are not live reads
    further from the roofline."""
    h, dk, dv = _linear(spec)
    per_slot = 2 * h * dk * dv * STATE_BYTES + rows * h * (2 * dk + dv) * 4
    return float(live_slots) * layer_counts(spec)[0] * per_slot


def chip_share(spec: dict) -> dict | None:
    """One chip holds the whole configuration; the family refuses a mesh."""
    if any(size > 1 for size in costs.mesh_axes(spec).values()):
        return None
    return {"weights": 1, "kv": 1, "heads": 1}
