"""Operations and bytes of the Granite 4.0-H decoder (Mamba-2 state-space
layers around one attention layer a period, a shared SwiGLU, the head tied
to the embedding), from a configuration file's published ``config.json``
keys: the functions ``costs.py`` documents, found through
``costs.of(config)`` by the configuration's ``"costs"`` key, and four for
the recurrent state and the scan's two kernels.

At granite-4.0-h-micro's sizes (benchmark/tests/test_granite_cell.py holds
this file to the hand figures of ISSUE 61): a Mamba-2 mixer 2048 x 8512
(W_in: z 4096, xBC 4352, dt 64) + 4352 x 4 + 4352 (the convolution and its
bias) + 3 x 64 (A_log, dt_bias, D) + 4096 (the gated norm) + 4096 x 2048
(W_out) = 25,847,232; the shared MLP 2048 x 16384 + 8192 x 2048 =
50,331,648; two block norms 4,096: a Mamba-2 layer 76,182,976. An attention
mixer 2 x 2048^2 + 2 x 2048 x 512 = 10,485,760: an attention layer
60,821,504. Embedding (tied, counted once) 205,520,896; final norm 2,048.
36 x 76,182,976 + 4 x 60,821,504 + 205,522,944 = 3,191,396,096.

IT DEFINES NO ``kv_launch_bytes`` THAT HOLDS THE STATE: that function also
feeds ``kernel.ragged_decode_roofline_pct``, which the state's bytes would
push past 100. So ``step.verify_mem_mfu_pct`` leaves the state out in this
configuration's cells and reads low by ``ssm.state_bytes_pct`` of the
launch's bytes (PERF.md section 7 owes that to a ``benchmark`` PR)."""

from __future__ import annotations

import costs

DTYPE_BYTES = costs.DTYPE_BYTES
STATE_BYTES = 4          # the state is float32, as the published kernels keep it


def _ssm(spec: dict) -> tuple[int, int, int, int]:
    """(heads, head size, state size, groups)."""
    return (spec["mamba_n_heads"], spec["mamba_d_head"], spec["mamba_d_state"],
            spec.get("mamba_n_groups", 1))


def conv_channels(spec: dict) -> int:
    h, p, n, g = _ssm(spec)
    return h * p + 2 * g * n


def mlp_params(spec: dict) -> int:
    return 3 * spec["hidden_size"] * spec["shared_intermediate_size"]


def mamba_layer_params(spec: dict) -> int:
    e = spec["hidden_size"]
    h, p, _, _ = _ssm(spec)
    c = conv_channels(spec)
    mixer = (e * (h * p + c + h) + c * spec["mamba_d_conv"] + c + 3 * h
             + h * p + h * p * e)
    return mixer + 2 * e + mlp_params(spec)


def attention_layer_params(spec: dict) -> int:
    e, d = spec["hidden_size"], costs.head_dim(spec)
    heads, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    return 2 * e * heads * d + 2 * e * kv * d + 2 * e + mlp_params(spec)


def layer_counts(spec: dict) -> tuple[int, int]:
    """(state-space layers, attention layers) of the layers held."""
    kinds = spec["layer_types"][:spec["num_hidden_layers"]]
    mamba = sum(k == "mamba" for k in kinds)
    return mamba, len(kinds) - mamba


embedding_params = costs.embedding_params      # embedding (tied), final norm


def total_params(spec: dict) -> int:
    mamba, attn = layer_counts(spec)
    return (mamba * mamba_layer_params(spec)
            + attn * attention_layer_params(spec) + embedding_params(spec))


def weight_bytes(spec: dict) -> int:
    return total_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def step_weight_bytes(spec: dict) -> int:
    """Weight bytes one decode or verify step must read: every layer and
    the output head (the tied embedding, read whole as the head); of the
    embedding as an embedding only the rows looked up."""
    b = DTYPE_BYTES[spec.get("dtype", "bfloat16")]
    head = spec["vocab_size"] * spec["hidden_size"]
    return (total_params(spec) - embedding_params(spec) + head) * b


def kv_bytes_per_token(spec: dict, kv_dtype_bytes: int = 2) -> int:
    """Keys and values of one position over the layers that OWN pages, as
    the equations have them: the attention layers alone (four of forty:
    8,192 B). On the chip the pool stores a 64-wide head at 128 lanes
    (`kv_stored_bytes_per_token`: 16,384 B); the rooflines count the row
    once and unpadded, as ``deepseek_v2_costs`` counts its latent row, so
    the padding reads as distance from the roofline."""
    return (layer_counts(spec)[1] * 2 * spec["num_key_value_heads"]
            * costs.head_dim(spec) * kv_dtype_bytes)


def kv_stored_bytes_per_token(spec: dict, kv_dtype_bytes: int = 2) -> int:
    """What the pool holds, and a launch reads, of one position where
    kernels compile: the head rounded up to whole 128-lane tiles (Mosaic
    refuses a page's slice of 64 lanes: `engine._pool_head_dim`, PR 61):
    16,384 B. What ``ssm.state_bytes_pct`` counts as a launch's pages."""
    lanes = -(-costs.head_dim(spec) // 128) * 128
    return (layer_counts(spec)[1] * 2 * spec["num_key_value_heads"]
            * lanes * kv_dtype_bytes)


def state_bytes_per_slot(spec: dict, conv_dtype_bytes: int = 2,
                         conv: bool = False) -> int:
    """What one slot (or one snapshot) holds of its past in the Mamba-2
    layers: the float32 state [head size, state size] a head, every such
    layer (75,497,472 B at 36); with `conv` also the convolution's last
    K - 1 rows (940,032 B)."""
    h, p, n, _ = _ssm(spec)
    tail = (spec["mamba_d_conv"] - 1) * conv_channels(spec) * conv_dtype_bytes
    return layer_counts(spec)[0] * (
        h * p * n * STATE_BYTES + (tail if conv else 0))


def flash_prefill_flops(spec: dict, t: int) -> float:
    """One causal attention over a bucket of t positions, one attention
    layer. (This family admits through the mixed step; no flash-prefill
    call is expected in its cells.)"""
    return costs.flash_prefill_flops(spec, t)


def ssd_chunk_flops(spec: dict, rows: float) -> float:
    """Operations the scan's EQUATIONS need for `rows` tokens of one slot,
    every Mamba-2 layer: per token and head the decay (P N), the outer
    product (dt x) B^T added in (2 P N) and the read-out S C (2 P N): 5 P N.
    The chunked form spends more (a block's own pair terms, the products in
    float32 at six bf16 passes): that reads as distance from the roofline."""
    h, p, n, _ = _ssm(spec)
    return float(rows) * layer_counts(spec)[0] * h * 5.0 * p * n


def ssd_chunk_bytes(spec: dict, rows: float) -> float:
    """Bytes a chunk launch must move for the scan, every Mamba-2 layer:
    the slot's state read once and written once, and a row's x, B, C, dt
    in and y out (float32)."""
    h, p, n, g = _ssm(spec)
    per_row = (2 * h * p + 2 * g * n + h) * 4
    return layer_counts(spec)[0] * (
        2.0 * h * p * n * STATE_BYTES + float(rows) * per_row)


def ssd_step_bytes(spec: dict, live_slots: float, rows: int) -> float:
    """Bytes one step launch must move for the scan, every Mamba-2 layer:
    each LIVE slot's state read once and written once, and its rows' x, B,
    C and dt (float32). What any implementation must move; a kernel that
    also moves the states of slots that are not live, or a copy of B and C
    a head, reads further from the roofline."""
    h, p, n, g = _ssm(spec)
    per_slot = 2 * h * p * n * STATE_BYTES + rows * (h * p + 2 * g * n + h) * 4
    return float(live_slots) * layer_counts(spec)[0] * per_slot


def chip_share(spec: dict) -> dict | None:
    """One chip holds the whole configuration; the family refuses a mesh."""
    if any(size > 1 for size in costs.mesh_axes(spec).values()):
        return None
    return {"weights": 1, "kv": 1, "heads": 1}
